"""Sharding-aware checkpoint/auto-resume (SURVEY.md T3, section 5.4).

Reference stack: ``tf.train.Saver`` sharded V2 checkpoints, where each PS task
writes the variables it owns, ``CheckpointSaverHook`` triggers saves, and
``MonitoredTrainingSession`` restores the newest checkpoint on start.  Here
Orbax provides the same properties natively on a mesh: every host writes only
its local shards (OCDBT), saves are asynchronous (training continues during
the write — the reference's saver blocks the session), and restore re-shards
to whatever mesh layout the restoring job uses (``restore_latest`` takes the
target state/shardings as the template).
"""

from __future__ import annotations

import logging
import os
from typing import Any

import jax
import orbax.checkpoint as ocp

from .state import TrainState

log = logging.getLogger("dtx.checkpoint")


def _is_key(x: Any) -> bool:
    """True for typed PRNG key arrays (``jax.random.key``), which Orbax
    cannot serialize directly (their extended dtype has no numpy form)."""
    try:
        return jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key)
    except (AttributeError, TypeError):
        return False


def keys_to_data(state: Any) -> Any:
    """The storable form of a pytree: every typed PRNG key leaf replaced by
    its raw counter data (``jax.random.key_data``).  Non-key leaves pass
    through untouched."""
    return jax.tree.map(
        lambda x: jax.random.key_data(x) if _is_key(x) else x, state
    )


def data_to_keys(restored: Any, template: Any) -> Any:
    """Inverse of :func:`keys_to_data`: leaves that are typed keys in
    ``template`` are re-wrapped (``jax.random.wrap_key_data``) with the
    template leaf's RNG impl, so the restored state round-trips to the
    exact key type the trainer folds per step."""
    return jax.tree.map(
        lambda r, t: (
            jax.random.wrap_key_data(r, impl=jax.random.key_impl(t))
            if _is_key(t)
            else r
        ),
        restored,
        template,
    )


def flat_params_of(state_or_params: Any):
    """The flat parameter vector of a params pytree (or a TrainState — its
    ``params`` half), in the shared ``ps_shard.flat_param_spec`` leaf
    order — the bridge from a restored checkpoint to the serve plane's
    flat-vector substrate (the model registry publishes exactly this
    shape, and a serving replica's ``unflatten`` inverts it).  In the
    tree's own type where every leaf shares one (a model held in bfloat16
    is published, loaded and served in bfloat16); float32 for a tree of
    mixed types."""
    import numpy as np

    params = getattr(state_or_params, "params", state_or_params)
    leaves = jax.tree.leaves(params)
    if not leaves:
        raise ValueError("no parameter leaves to flatten")
    dtypes = {np.dtype(l.dtype) for l in leaves}
    dtype = dtypes.pop() if len(dtypes) == 1 else np.dtype(np.float32)
    return np.concatenate(
        [np.asarray(jax.device_get(l), dtype).reshape(-1) for l in leaves]
    )


class CheckpointManager:
    """Thin policy wrapper over ``ocp.CheckpointManager``.

    - ``save(step, state)``: async, deduped, honors max_to_keep.
    - ``restore_latest(template)``: returns restored state with the
      *template's* shardings (elastic re-shard on restore), or None.
    """

    def __init__(
        self,
        directory: str,
        *,
        max_to_keep: int = 5,
        save_interval_steps: int = 1,
        async_save: bool = True,
    ):
        opts = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            save_interval_steps=save_interval_steps,
            enable_async_checkpointing=async_save,
        )
        self._mgr = ocp.CheckpointManager(os.path.abspath(directory), options=opts)

    def save(self, step: int, state: TrainState, *, force: bool = False) -> bool:
        step = int(step)
        if self._mgr.latest_step() == step:
            return False  # already saved this step (periodic + final overlap)
        # Typed PRNG keys are stored as their raw key data (JAX's extended
        # key dtype has no numpy/tensorstore form); restore re-wraps them.
        return self._mgr.save(
            step, args=ocp.args.StandardSave(keys_to_data(state)), force=force
        )

    def restore_latest(self, template: TrainState) -> TrainState | None:
        step = self._mgr.latest_step()
        if step is None:
            return None
        abstract = jax.tree.map(
            ocp.utils.to_shape_dtype_struct, keys_to_data(template)
        )
        restored = self._mgr.restore(step, args=ocp.args.StandardRestore(abstract))
        log.info("restored checkpoint at step %d", step)
        return data_to_keys(restored, template)

    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def wait(self) -> None:
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.close()
