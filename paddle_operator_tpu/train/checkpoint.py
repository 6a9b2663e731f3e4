"""Checkpoint / resume.

The reference has no checkpoint support in the operator — its design docs
assume "params periodically saved into a distributed file system"
(docs/design-fault-tolerant.md:19, docs/design-arch.md:58) and leave the
plumbing to user PV/PVCs (docs/user-guide.md:260-347).  Here the contract is
first-class end to end:

- the CRD carries ``spec.checkpointPath``; the controller injects it as
  ``TPUJOB_CHECKPOINT_PATH`` (controller/builders.py);
- this module gives the workload side save/restore of the sharded
  TrainState via orbax (async, multi-host-aware, preserves shardings);
- on a controller-driven restart (maxRestarts budget), pods come back with
  identical ranks, ``latest_step`` finds the newest complete checkpoint,
  and training resumes — realizing the recovery loop the reference only
  sketches.
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax

from paddle_operator_tpu.utils import tracing as TR


class CheckpointManager:
    """Thin orbax wrapper bound to the injected checkpoint path."""

    def __init__(self, path: Optional[str] = None, *, max_to_keep: int = 3,
                 save_interval_steps: int = 1000) -> None:
        self.path = path or os.environ.get("TPUJOB_CHECKPOINT_PATH", "")
        self._mgr = None
        self.save_interval_steps = save_interval_steps
        if self.path:
            import orbax.checkpoint as ocp

            self._mgr = ocp.CheckpointManager(
                self.path,
                options=ocp.CheckpointManagerOptions(
                    max_to_keep=max_to_keep,
                    save_interval_steps=save_interval_steps,
                    enable_async_checkpointing=True,
                ),
            )

    @property
    def enabled(self) -> bool:
        return self._mgr is not None

    def latest_step(self) -> Optional[int]:
        if not self._mgr:
            return None
        return self._mgr.latest_step()

    def all_steps(self) -> list:
        """Committed checkpoint steps, ascending (restore fallback walks
        this backwards when the newest step turns out corrupt)."""
        if not self._mgr:
            return []
        return sorted(self._mgr.all_steps())

    def save(self, step: int, state: Any, *, force: bool = False) -> bool:
        """Save (async).  Returns True if a save was actually scheduled
        (the manager applies save_interval_steps unless forced).

        On the CPU backend the state is snapshotted to host numpy first:
        CPU ``jax.Array`` shards are ZERO-COPY views, so an async save
        racing a training loop that DONATES the state into the next step
        (trainer.fit does) would read buffers XLA has already reused —
        silent corruption or a heap abort.  On TPU/GPU the async writer's
        blocking D2H copy makes the snapshot redundant, and multi-process
        arrays are not host-gatherable, so both skip it."""
        if not self._mgr:
            return False
        import orbax.checkpoint as ocp

        will_save = force or getattr(self._mgr, "should_save",
                                     lambda s: True)(step)
        if not will_save:
            return self._mgr.save(step, args=ocp.args.StandardSave(state),
                                  force=force)
        # what the caller's thread pays for a save: the snapshot and
        # whatever of the copy-out the async writer does before returning
        with TR.phase("train.checkpoint_save", step=step):
            if jax.default_backend() == "cpu" and jax.process_count() == 1:
                import numpy as np

                state = jax.tree_util.tree_map(
                    lambda x: np.array(x) if isinstance(x, jax.Array)
                    else x, state)
            return self._mgr.save(step, args=ocp.args.StandardSave(state),
                                  force=force)

    def restore(self, state_like: Any, step: Optional[int] = None) -> Any:
        """Restore into the sharding/structure of `state_like` (an abstract
        or concrete TrainState).  Returns the restored state.

        Pre-r5 int8-moment checkpoints stored the Adam moments in the
        FLAT ``[n_blocks, BLOCK]`` layout (train/opt8bit.py VERSION
        NOTE); a shape-mismatch restore against the current shard-aware
        template retries against the legacy template and re-blocks the
        moments once, so old checkpoints keep resuming."""
        if not self._mgr:
            raise RuntimeError("checkpointing disabled (no path)")
        import orbax.checkpoint as ocp

        step = step if step is not None else self._mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {self.path}")
        try:
            return self._mgr.restore(
                step, args=ocp.args.StandardRestore(state_like))
        except Exception as err:
            if not (hasattr(state_like, "params")
                    and hasattr(state_like, "opt_state")):
                raise
            from paddle_operator_tpu.train import opt8bit

            legacy, found = opt8bit.legacy_flat_template(state_like)
            if not found:
                raise
            try:
                raw = self._mgr.restore(
                    step, args=ocp.args.StandardRestore(legacy))
            except Exception:
                # not an r4-layout checkpoint either: the ORIGINAL
                # failure is the real story — surface it, not the
                # legacy template's mismatch
                raise err
            return opt8bit.reblock_restored(raw, state_like)

    def restore_params(self, params_like: Any,
                       step: Optional[int] = None) -> Any:
        """Restore ONLY the ``params`` subtree of a saved TrainState into
        `params_like` — ``jax.ShapeDtypeStruct`` leaves carrying the
        sharding AND the dtype wanted (orbax casts on the way in, so f32
        masters land directly in a server's bf16).  The optimizer state
        is never read: a server holds none, and whether the checkpoint
        was trained with f32 or int8 moments does not matter to it."""
        if not self._mgr:
            raise RuntimeError("checkpointing disabled (no path)")
        import orbax.checkpoint as ocp

        step = step if step is not None else self._mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {self.path}")
        restore_args = jax.tree.map(
            lambda s: ocp.ArrayRestoreArgs(
                sharding=s.sharding, dtype=s.dtype, global_shape=s.shape),
            params_like)
        return self._mgr.restore(step, args=ocp.args.PyTreeRestore(
            item={"params": params_like},
            restore_args={"params": restore_args},
            partial_restore=True))["params"]

    def wait(self) -> None:
        """Block until pending async saves are durable (call before exit)."""
        if self._mgr:
            self._mgr.wait_until_finished()

    def close(self) -> None:
        """Flush pending async saves, then close.  ``wait()`` first is
        load-bearing: orbax's close() does not drain the async commit, so
        an exiting trainer that saved-then-closed would silently drop its
        newest checkpoint — exactly the step a preemption drain forced."""
        if self._mgr:
            self._mgr.wait_until_finished()
            self._mgr.close()


def abstract_like(tree: Any) -> Any:
    """`tree` as a restore template: shape, dtype and sharding of every
    array leaf, no buffers."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        if isinstance(x, jax.Array) else x, tree)


def restore_newest(ckpt: CheckpointManager, restore_step, *, logger=None):
    """``restore_step(step)`` over the committed steps, newest first.

    A corrupt/partial newest step (torn write during the kill that caused
    this very restart) falls back to the previous complete step with a
    logged warning instead of failing the whole restart; only when every
    step fails does the newest step's error surface."""
    if logger is None:
        # The fallback must never be silent: rolling back to an
        # older step re-does (or serves stale) work and the operator
        # needs the trace even from callers that pass no logger.
        from paddle_operator_tpu.utils.observability import get_logger

        logger = get_logger()
    steps = ckpt.all_steps() or [ckpt.latest_step()]
    first_err: Optional[Exception] = None
    for step in reversed(steps):
        try:
            return restore_step(step)
        except Exception as err:
            if first_err is None:
                first_err = err
            logger.warning(
                f"checkpoint step {step} failed to restore "
                f"({type(err).__name__}: {err}); trying the "
                f"previous complete step")
    raise first_err


def resume_or_init(ckpt: CheckpointManager, init_fn, state_like=None, *,
                   logger=None):
    """The restart-recovery entry: restore the latest checkpoint if one
    exists (falling back over corrupt steps, :func:`restore_newest`), else
    initialize fresh.  `init_fn()` builds a fresh sharded state;
    `state_like` pins structure and shardings for restore — pass an
    abstract one (``trainer.abstract_state``) where memory matters.
    Without it the template is taken from ``init_fn()`` and the fresh
    state is dropped BEFORE the restore, so the device never holds two
    copies."""
    if ckpt.enabled and ckpt.latest_step() is not None:
        like = state_like if state_like is not None \
            else abstract_like(init_fn())
        return restore_newest(
            ckpt, lambda step: ckpt.restore(like, step=step),
            logger=logger), True
    return init_fn(), False
