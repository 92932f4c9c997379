"""ResNet (``"model": "resnet"``), trained on images.

The only file that names the program's ``models/resnet.py`` and the
reference ``reference/resnet_ref.py``.  A configuration's ``program`` group
holds the arguments of the program's ``Config``; ``train`` the optimizer's.
"""

from __future__ import annotations

import copy

from benchmarks.harness import traffic as traffic_lib
from benchmarks.reference import resnet_ref, weights

#: The rehearsal's size.  At batch 8 the program's bf16 elementwise rounding
#: drowns any int8 control, so the tiny ResNet states float32 and its
#: control is bfloat16.
TINY_PROGRAM = {"num_classes": 10, "stage_sizes": [1, 1], "width": 8,
                "stem": "s2d", "bn_momentum": 0.9, "compute_dtype": "float32"}
TINY_PRECISION = {"params": "float32", "compute": "float32", "control": "bfloat16"}
TINY_DATA = {"n": 64, "image_size": 32, "num_classes": 10}
#: Limits of the tiny size, read on the CPU (benchmarks/tests/test_control.py
#: holds the readings): above the sound runs, below the control.
TINY_LIMITS = {"loss_gap": 1e-5, "grad_gap_kernels": 0.003,
               "delta_gap_kernels": 0.5, "grad_cosine_median": 0.999}


def build(config: dict):
    """``(cfg, tree_fn)`` with ``tree_fn(key) -> (params, model_state)``."""
    from distributed_tensorflow_examples_tpu import models

    c = dict(config["program"])
    c["stage_sizes"] = tuple(c["stage_sizes"])
    cfg = models.resnet.Config(**c)
    return cfg, lambda key: weights.resnet_trees(c, key)


def loss_fn(cfg, config: dict):
    from distributed_tensorflow_examples_tpu import models

    return models.resnet.loss_fn(cfg, l2=config["train"]["l2"])


def sharding_rules(cfg):
    from distributed_tensorflow_examples_tpu import models

    return models.resnet.sharding_rules(cfg)


def batches(config: dict, traffic: dict, seed: int):
    """``(arrays, rows_of)``: the seeded host arrays the input pipeline
    draws batches from, and ``rows_of(batch)``, which reads from a fed
    batch's content which rows of them it holds."""
    arrays = traffic_lib.images(traffic["data"], seed)
    n = len(arrays["image"])
    return arrays, lambda batch: traffic_lib.image_rows(batch["image"], n)


def forward_macs(c: dict, image_size: int) -> int:
    """Multiply-adds of one image's forward pass: every convolution and the
    head (ResNet-50 at 224: about 4.1e9)."""
    def conv(h_out, kh, cin, cout):
        return h_out * h_out * kh * kh * cin * cout

    h = image_size // 2
    macs = conv(h, 7, 3, c["width"])
    h //= 2  # max pool
    cin = c["width"]
    for _key, cin, mid, stride, has_proj in weights.resnet_blocks(c):
        macs += conv(h, 1, cin, mid)
        h //= stride
        macs += conv(h, 3, mid, mid) + conv(h, 1, mid, 4 * mid)
        if has_proj:
            macs += conv(h, 1, cin, 4 * mid)
        cin = 4 * mid
    return macs + cin * c["num_classes"]


def train_flops_per_example(config: dict, traffic: dict) -> float:
    """Forward plus backward of one image: every product of the forward pass
    once and of the backward pass twice, a multiply-add two operations;
    recomputation is not counted."""
    return 3.0 * 2.0 * forward_macs(config["program"], traffic["data"]["image_size"])


def reference_train(config: dict, seed: int, batches: list, mode: str) -> dict:
    """The reference's numbers over ``batches``: the rows of ``arrays`` that
    each compared step was fed, as ``{"image", "label"}``."""
    c = {k: config["program"][k] for k in ("num_classes", "stage_sizes", "width")}
    pairs = [(b["image"], b["label"]) for b in batches]
    return resnet_ref.train(c, config["train"], seed, pairs, mode)


def compared_kernels(config: dict):
    """Which leaves the norms' gaps and the cosine are taken over, by name:
    the kernels.  A batch-norm scale's or bias's gradient is a sum with heavy
    cancellation, a fifth to a third off in ANY precision under float32
    (PERF.md section 2)."""
    return lambda leaf: leaf.endswith("kernel")


def tiny(config: dict) -> dict:
    """The configuration at the rehearsal's size; ``rehearsal`` holds the
    limits read at it and the data's size."""
    out = copy.deepcopy(config)
    out["program"] = dict(TINY_PROGRAM)
    out["precision"] = dict(TINY_PRECISION)
    out["rehearsal"] = {"limits": dict(TINY_LIMITS), "data": dict(TINY_DATA)}
    return out
