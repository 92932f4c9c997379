"""From a configuration file to the program's own objects.

The only place where the benchmark names the program's model modules.  A
configuration's ``model`` picks the adapter; its ``program`` group holds
the arguments of the program's ``Config``.
"""

from __future__ import annotations

from benchmarks.reference import weights


def transformer(config: dict, overrides: dict | None = None):
    """``(cfg, tree_fn)``: the program's ``Config`` and the seeded-weights
    builder ``tree_fn(key) -> params`` of the same tree."""
    from distributed_tensorflow_examples_tpu import models

    c = dict(config["program"])
    cfg = models.transformer.Config(**c, **(overrides or {}))
    return cfg, lambda key: weights.transformer_tree(c, key)


def resnet(config: dict):
    """``(cfg, tree_fn)`` with ``tree_fn(key) -> (params, model_state)``."""
    from distributed_tensorflow_examples_tpu import models

    c = dict(config["program"])
    c["stage_sizes"] = tuple(c["stage_sizes"])
    cfg = models.resnet.Config(**c)
    return cfg, lambda key: weights.resnet_trees(c, key)
