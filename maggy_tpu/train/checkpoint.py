"""Checkpointing and experiment resume.

The reference has **no model checkpointing** (SURVEY.md §5.4 — users hand-roll
saves inside train_fn); here it is first-class:

* :class:`Checkpointer` — orbax-backed async save/restore of (sharded)
  TrainStates into a trial directory; restore rebuilds arrays directly on
  their mesh devices from the abstract target.
* experiment resume — ``HyperparameterOptConfig(resume_from=<exp_dir>)``
  preloads that experiment's persisted ``trial.json`` records into the new
  driver's final store, so finished trials are never re-run (the driver skips
  suggestions whose trial id already finalized).
"""

from __future__ import annotations

import json
import os
import time
import warnings
from typing import Any, Dict, List, Optional

# sidecar directory (non-numeric name: invisible to orbax's step scan)
# holding one JSON per step with the system config the state was saved under
_META_DIR = "system_meta"


class Checkpointer:
    """Thin orbax wrapper bound to one directory (per trial or per run)."""

    def __init__(self, directory: str, max_to_keep: int = 3, async_save: bool = True):
        import orbax.checkpoint as ocp

        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._manager = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                enable_async_checkpointing=async_save,
            ),
        )

    def save(self, step: int, state: Any, meta: Optional[Dict[str, Any]] = None) -> None:
        """Save ``state`` at ``step``. ``meta`` — the active system config
        (``Trainer.checkpoint_meta()``: ShardingSpec axes, n_microbatches,
        dtype) — is recorded in a JSON sidecar so a later restore can warn
        when the live configuration differs from the one that wrote the
        checkpoint."""
        import orbax.checkpoint as ocp

        from maggy_tpu import telemetry

        # an async save's span is its blocking (dispatch) cost — the part
        # that actually steals step time
        with telemetry.get().span("checkpoint_save", step=int(step)):
            self._manager.save(int(step), args=ocp.args.StandardSave(state))
        if meta is not None:
            self._write_meta(int(step), meta)

    # ------------------------------------------------------------------ meta

    def _meta_path(self, step: int) -> str:
        return os.path.join(self.directory, _META_DIR, f"{int(step)}.json")

    def _write_meta(self, step: int, meta: Dict[str, Any]) -> None:
        from maggy_tpu.util import _jsonify

        path = self._meta_path(step)
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w") as f:
                json.dump(_jsonify(meta), f, sort_keys=True)
        except OSError:
            pass  # metadata is advisory; never fail a save over it

    def saved_meta(self, step: Optional[int] = None) -> Optional[Dict[str, Any]]:
        """The system-config metadata recorded with ``step`` (default:
        latest), or None for checkpoints saved without it."""
        step = int(step) if step is not None else self.latest_step()
        if step is None:
            return None
        try:
            with open(self._meta_path(step)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    # mesh/world-size keys get the dedicated warn-and-reshard signal in
    # _check_reshard; _check_meta covers the rest (microbatch, dtype, ...)
    _LAYOUT_KEYS = ("mesh_axes", "num_devices", "n_processes")

    def _check_meta(self, step: int, expect_meta: Dict[str, Any]) -> None:
        """Warn (never fail) when the checkpoint's recorded system config
        disagrees with the live one on any shared key — restoring across
        mesh shapes or microbatch settings is *supported* (adopt_state /
        convert_pipeline_state re-place the arrays), but doing it silently
        has burned enough people that the mismatch deserves a signal."""
        from maggy_tpu.util import _jsonify

        saved = self.saved_meta(step)
        if not saved or not expect_meta:
            return
        expect = _jsonify(expect_meta)
        diffs = [
            f"{k}: saved={saved[k]!r} live={expect[k]!r}"
            for k in sorted(set(saved) & set(expect) - set(self._LAYOUT_KEYS))
            if saved[k] != expect[k]
        ]
        if diffs:
            warnings.warn(
                f"checkpoint step {step} was saved under a different system "
                f"config than the live one ({'; '.join(diffs)}); the state "
                "will be re-placed onto the live mesh, but training dynamics "
                "(batch/microbatch semantics) may differ",
                stacklevel=3,
            )

    @staticmethod
    def _template_layout(state_template: Any) -> Optional[Dict[str, Any]]:
        """The live mesh layout implied by the restore template's leaf
        shardings (None when the template carries no mesh — e.g. plain
        numpy trees in unit tests)."""
        import jax

        for leaf in jax.tree.leaves(state_template):
            sharding = getattr(leaf, "sharding", None)
            mesh = getattr(sharding, "mesh", None)
            if mesh is not None and getattr(mesh, "shape", None) is not None:
                try:
                    return {
                        "mesh_axes": {
                            k: v for k, v in dict(mesh.shape).items() if v > 1
                        },
                        "num_devices": int(mesh.size),
                        "n_processes": int(jax.process_count()),
                    }
                except (TypeError, ValueError):
                    return None
        return None

    def _check_reshard(self, step: int, state_template: Any) -> None:
        """Warn-and-reshard (docs/resilience.md): when the sidecar meta
        records a different mesh/world size than the template's live mesh,
        say so explicitly — the restore still proceeds (device_put onto the
        template's shardings re-places every leaf), but a silent cross-mesh
        restore has mis-sharded enough runs that the transition deserves a
        loud signal and a counter. This is the world-size-independent
        restore the elastic membership reshape rides."""
        from maggy_tpu import telemetry

        saved = self.saved_meta(step)
        live = self._template_layout(state_template)
        if not saved or not live:
            return
        diffs = [
            f"{k}: saved={saved[k]!r} live={live[k]!r}"
            for k in ("mesh_axes", "num_devices", "n_processes")
            if saved.get(k) is not None and saved[k] != live[k]
        ]
        if diffs:
            telemetry.get().count("resilience.ckpt_reshards")
            warnings.warn(
                f"checkpoint step {step} was saved on a different mesh "
                f"({'; '.join(diffs)}); resharding every leaf onto the live "
                "mesh during restore",
                stacklevel=3,
            )

    def restore(
        self,
        state_template: Any,
        step: Optional[int] = None,
        expect_meta: Optional[Dict[str, Any]] = None,
    ) -> Any:
        """Restore onto the template's shardings (pass an abstract or concrete
        state built by ``Trainer.make_state``). Pass the live trainer's
        ``checkpoint_meta()`` as ``expect_meta`` to be warned when the
        checkpoint was written under a different sharding/microbatch/dtype
        configuration.

        Fallback (docs/resilience.md): when no explicit ``step`` was
        requested and the latest retained step is unreadable/partial (a save
        interrupted by the very crash being recovered from), older retained
        steps are tried newest-first — each skip warns and counts a
        ``checkpoint_fallback`` telemetry counter. An explicitly requested
        step never falls back."""
        import orbax.checkpoint as ocp

        from maggy_tpu import telemetry

        explicit = step is not None
        step = int(step) if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"No checkpoint found under {self.directory}")
        candidates = (
            [step]
            if explicit
            else sorted((s for s in self.all_steps() if s <= step), reverse=True)
        )
        last_err: Optional[BaseException] = None
        for i, s in enumerate(candidates):
            self._check_reshard(s, state_template)
            if expect_meta is not None:
                self._check_meta(s, expect_meta)
            try:
                with telemetry.get().span("checkpoint_restore", step=s):
                    return self._manager.restore(
                        s, args=ocp.args.StandardRestore(state_template)
                    )
            # broad: orbax surfaces corrupt/truncated checkpoints as many
            # types (ValueError, json/msgpack decode errors, zarr/tensorstore
            # failures) — anything but success means "this step is gone"
            except Exception as e:  # noqa: BLE001
                last_err = e
                if explicit or i == len(candidates) - 1:
                    raise
                telemetry.get().count("checkpoint_fallback")
                warnings.warn(
                    f"checkpoint step {s} under {self.directory} is "
                    f"unreadable ({type(e).__name__}: {e}); falling back to "
                    f"the previous retained step {candidates[i + 1]}",
                    stacklevel=2,
                )
        raise last_err  # unreachable; keeps the control flow explicit

    def restore_params(self, step: Optional[int] = None) -> Any:
        """Params-only restore for serving: pull just the ``params`` subtree
        out of a saved TrainState without rebuilding the trainer/optimizer,
        unboxing flax ``Partitioned`` wrappers (template-free restores
        return them as ``{"value": array}`` dicts) down to raw arrays —
        exactly what ``model.apply({"params": ...})`` and the serve engine
        take."""
        from maggy_tpu import telemetry

        step = int(step) if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"No checkpoint found under {self.directory}")
        with telemetry.get().span("checkpoint_restore_params", step=step):
            restored = self._manager.restore(step)
        tree = restored if isinstance(restored, dict) else restored.__dict__
        if "params" not in tree:
            raise ValueError(
                f"checkpoint at step {step} has no 'params' subtree "
                f"(keys: {sorted(tree)})"
            )

        def unbox(node):
            if isinstance(node, dict):
                if "value" in node and not isinstance(node["value"], dict):
                    return node["value"]
                return {k: unbox(v) for k, v in node.items()}
            return node

        return unbox(tree["params"])

    def latest_step(self) -> Optional[int]:
        return self._manager.latest_step()

    def all_steps(self) -> List[int]:
        return list(self._manager.all_steps())

    def wait(self) -> None:
        self._manager.wait_until_finished()

    def close(self) -> None:
        self._manager.wait_until_finished()
        self._manager.close()


_DENSE_ZERO = {"stage": 0, "bucket_mb": None, "shards": 1}


def restore_zero_compat(
    checkpointer: Checkpointer,
    state_template: Any,
    *,
    live_meta: Optional[Dict[str, Any]] = None,
    step: Optional[int] = None,
) -> Any:
    """Restore a TrainState across ``zero_stage`` / bucket / data-width
    transitions (docs/distributed.md "Gradient overlap & ZeRO").

    Under ``zero_stage=1`` the optimizer state is saved as flat
    data-sharded bucket vectors (parallel/overlap.py), a layout keyed by
    the bucketing plan — which changes with ``bucket_mb`` and the data-axis
    width. The sidecar meta records that layout (``checkpoint_meta()["zero"]``,
    PR 9's provenance discipline); when it differs from the live trainer's,
    this wrapper restores into a template of the SAVED layout, warns,
    counts ``resilience.ckpt_zero_reshards``, and converts dense↔flat (or
    flat↔flat across plans) before re-placing onto the live template's
    shardings. With matching layouts it is exactly ``Checkpointer.restore``.
    """
    import jax
    import numpy as np

    from maggy_tpu import telemetry
    from maggy_tpu.parallel import overlap

    live_zero = dict((live_meta or {}).get("zero") or _DENSE_ZERO)
    resolved = int(step) if step is not None else checkpointer.latest_step()
    saved_meta = checkpointer.saved_meta(resolved) if resolved is not None else None
    saved_zero = dict((saved_meta or {}).get("zero") or _DENSE_ZERO)
    if saved_zero == live_zero:
        return checkpointer.restore(
            state_template, step=step, expect_meta=live_meta
        )

    params = state_template.params
    abstract_params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params
    )

    def opt_template(zero: Dict[str, Any]):
        if int(zero.get("stage") or 0) == 0:
            abstract = jax.eval_shape(state_template.tx.init, abstract_params)
            return None, abstract
        plan = overlap.plan_buckets(
            abstract_params,
            zero.get("bucket_mb"),
            pad_to=max(1, int(zero.get("shards") or 1)),
        )
        flats = {
            b.name: jax.ShapeDtypeStruct((b.padded_size,), b.dtype)
            for b in plan.buckets
        }
        return plan, jax.eval_shape(state_template.tx.init, flats)

    saved_plan, saved_abstract = opt_template(saved_zero)
    live_shardings = jax.tree.map(
        lambda x: getattr(x, "sharding", None), state_template.opt_state
    )
    # concrete zeros (replicated) stand in for the saved layout: orbax
    # overwrites every leaf, and the conversion below re-places the result
    saved_opt = jax.tree.map(
        lambda s: np.zeros(s.shape, s.dtype), saved_abstract
    )
    restored = checkpointer.restore(
        state_template.replace(opt_state=saved_opt),
        step=step,
        expect_meta=live_meta,
    )
    telemetry.get().count("resilience.ckpt_zero_reshards")
    warnings.warn(
        f"checkpoint step {resolved} holds a {_zero_desc(saved_zero)} "
        f"optimizer-state layout; converting to the live {_zero_desc(live_zero)} "
        "layout during restore",
        stacklevel=2,
    )
    opt = restored.opt_state
    if saved_plan is not None:
        opt = overlap.unflatten_opt_state(opt, saved_plan, params)
    live_plan, _ = opt_template(live_zero)
    if live_plan is not None:
        opt = overlap.flatten_opt_state(opt, live_plan, params)
    if all(s is not None for s in jax.tree.leaves(live_shardings)):
        opt = jax.tree.map(jax.device_put, opt, live_shardings)
    return restored.replace(opt_state=opt)


def _zero_desc(zero: Dict[str, Any]) -> str:
    if int(zero.get("stage") or 0) == 0:
        return "dense (zero_stage=0)"
    return (
        f"ZeRO-1 (shards={zero.get('shards')}, bucket_mb={zero.get('bucket_mb')})"
    )


def load_finalized_trials(exp_dir: str) -> list:
    """Load every persisted trial.json under a previous experiment directory
    (the driver's persistence format, hpo.py _persist_trial). Goes through the
    Env abstraction so gs:// experiment dirs resume too."""
    import json

    from maggy_tpu.core.env import EnvSing
    from maggy_tpu.trial import Trial

    env = EnvSing.get_instance()
    out = []
    if not env.exists(exp_dir):
        raise FileNotFoundError(f"resume_from directory does not exist: {exp_dir}")
    for name in env.listdir(exp_dir):
        path = os.path.join(exp_dir, name, "trial.json")
        if not env.exists(path):
            continue
        try:
            trial = Trial.from_dict(env.load_json(path))
        except (json.JSONDecodeError, KeyError, ValueError):
            continue
        if trial.status in (Trial.FINALIZED, Trial.ERROR):
            out.append(trial)
    return out
