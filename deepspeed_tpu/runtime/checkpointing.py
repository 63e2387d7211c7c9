"""Checkpoint save/load.

Analog of reference ``runtime/engine.py:3068 save_checkpoint`` /
``:2708 load_checkpoint`` + the pluggable ``CheckpointEngine``
(``runtime/checkpoint_engine/checkpoint_engine.py``).  TPU-native storage is
orbax: sharded arrays are written by all hosts cooperatively (the analog of each
rank writing its ``zero_pp_rank_*`` partition file) and restored with *current*
shardings — which gives elastic / universal-checkpoint resharding (reference
``checkpoint/deepspeed_checkpoint.py``) for free: save under one mesh, load under
another, orbax + XLA redistribute.

Layout (per the reference's tag-directory protocol)::

    <save_dir>/
      latest                # text file containing the newest tag (engine.py:3105)
      <tag>/
        state/              # orbax pytree: params, opt_state, scaler, step
        ds_meta.json        # counters, config snapshot, client_state
"""

from __future__ import annotations

import importlib.machinery
import importlib.util
import json
import os
from abc import ABC, abstractmethod
from typing import Any, Dict, Optional

import jax
import numpy as np

from ..telemetry import trace as trace_mod
from ..utils.logging import log_dist, logger


class CheckpointEngine(ABC):
    """Pluggable storage backend (reference ``checkpoint_engine.py``)."""

    def __init__(self, config_params=None):
        pass

    @abstractmethod
    def save(self, state_tree, path: str) -> None:
        ...

    @abstractmethod
    def load(self, path: str, abstract_target=None):
        ...

    def makedirs(self, path: str, exist_ok: bool = True) -> None:
        os.makedirs(path, exist_ok=exist_ok)

    def commit(self, tag: str) -> bool:
        return True


class OrbaxCheckpointEngine(CheckpointEngine):
    """Async-capable sharded-array storage via orbax (the Nebula-engine analog —
    reference ``nebula_checkpoint_engine.py`` — is subsumed: orbax is already
    async + multi-host)."""

    def __init__(self, config_params=None, use_async: bool = False):
        super().__init__(config_params)
        import orbax.checkpoint as ocp

        self._ocp = ocp
        self._ckptr = ocp.StandardCheckpointer()

    def save(self, state_tree, path: str) -> None:
        path = os.path.abspath(path)
        self._ckptr.save(path, state_tree, force=True)
        self._ckptr.wait_until_finished()

    def load(self, path: str, abstract_target=None):
        path = os.path.abspath(path)
        if abstract_target is not None:
            return self._ckptr.restore(path, target=abstract_target)
        return self._ckptr.restore(path)


def _require_orbax() -> None:
    """Raise what ``import orbax.checkpoint`` would raise where the library
    is missing, WITHOUT importing it (the import is ~12 s on a v5e host).
    ``orbax`` is a namespace package, so ``find_spec("orbax.checkpoint")``
    would import it as the parent and leave it in ``sys.modules``: the
    parent is found by name and the library under the parent's paths."""
    parent = importlib.util.find_spec("orbax")
    if parent is None:
        raise ModuleNotFoundError("No module named 'orbax'", name="orbax")
    if importlib.machinery.PathFinder.find_spec(
            "orbax.checkpoint", parent.submodule_search_locations or ()) is None:
        raise ModuleNotFoundError("No module named 'orbax.checkpoint'",
                                  name="orbax.checkpoint")


class CheckpointManager:
    """Engine-facing checkpoint orchestration with the reference's tag protocol.

    The storage backend is built at its FIRST USE, not here: the first
    ``save`` or ``load`` of a process carries the checkpoint library's import
    (~12 s on a v5e host; a ``checkpoint_engine`` span of the start-up ring,
    ``args.first_use`` = ``"save"`` | ``"load"``), and a run that never saves
    or loads never pays it.  Without an injected ``checkpoint_engine`` the
    constructor checks that the library is installed without importing it, so
    a missing one still fails at ``initialize`` and not at the first save."""

    def __init__(self, engine, checkpoint_engine: Optional[CheckpointEngine] = None):
        self.engine = engine
        self._checkpoint_engine = checkpoint_engine
        if checkpoint_engine is None:
            _require_orbax()

    def _backend(self, first_use: str) -> CheckpointEngine:
        """The storage backend: the one given, or the default one, built
        ONCE under a ``checkpoint_engine`` span of the start-up ring by
        whoever reaches for it first (``first_use``)."""
        if self._checkpoint_engine is None:
            with trace_mod.setup_timeline().span("checkpoint_engine",
                                                 first_use=first_use):
                self._checkpoint_engine = OrbaxCheckpointEngine()
        return self._checkpoint_engine

    @property
    def checkpoint_engine(self) -> CheckpointEngine:
        return self._backend("attribute")

    # -- tag handling (reference engine.py:3050 _checkpoint_tag_validation) ----
    def _validate_tag(self, tag: str) -> None:
        mode = self.engine._config.checkpoint_config.tag_validation.lower()
        if mode == "ignore" or jax.process_count() == 1:
            return
        from .. import comm as dist

        import hashlib

        digest = hashlib.sha256(str(tag).encode()).digest()[:8]
        h = np.frombuffer(digest, dtype=np.int64)
        gathered = dist.all_gather_host(jax.numpy.asarray(h))
        valid = bool(np.all(np.asarray(gathered) == np.asarray(gathered)[0]))
        if not valid:
            msg = f"checkpoint tag '{tag}' is not consistent across processes"
            if mode == "fail":
                raise RuntimeError(msg)
            logger.warning(msg)

    def _default_dir(self, save_dir, for_load: bool = False):
        """``nebula.persistent_storage_path`` is the default checkpoint
        root when no directory is passed (reference nebula tier); loads
        prefer ``nebula.load_path`` when set (the reference's warm-start
        redirection, gated on ``enable_nebula_load``)."""
        if save_dir is not None:
            return save_dir
        neb = getattr(self.engine._config, "nebula_config", None)
        # a disabled nebula block carrying stale paths must not silently
        # redirect the default roots (the reference gates all nebula
        # behavior on enabled=true)
        if neb is not None and neb.enabled:
            if for_load and neb.enable_nebula_load and neb.load_path:
                return neb.load_path
            if neb.persistent_storage_path:
                return neb.persistent_storage_path
        raise ValueError(
            "save_checkpoint/load_checkpoint need a directory (or set "
            "nebula.persistent_storage_path as the default root)")

    def save(self, save_dir: str, tag: Optional[str] = None,
             client_state: Optional[Dict[str, Any]] = None,
             save_latest: bool = True) -> str:
        engine = self.engine
        save_dir = self._default_dir(save_dir)
        if tag is None:
            tag = f"global_step{engine.global_steps}"
        self._validate_tag(tag)
        ckpt_dir = os.path.join(save_dir, str(tag))
        backend = self._backend("save")
        backend.makedirs(ckpt_dir)

        backend.save(engine.state, os.path.join(ckpt_dir, "state"))
        if getattr(engine, "_offload_opt", None) is not None:
            # host-side optimizer partition (ZeRO-Offload/Infinity tier):
            # every process saves ITS ZeRO partition (reference writes
            # per-rank ``zero_pp_rank_*_optim_states.pt`` the same way)
            np.savez(os.path.join(
                ckpt_dir, f"offload_optimizer.p{jax.process_index()}.npz"),
                **engine._offload_opt.state_dict())
        if getattr(engine, "_block_opt", None) is not None and \
                jax.process_index() == 0:
            # streamed block params: fp32 master + moments (param-stream tier;
            # single-controller, so process 0 owns the whole store)
            np.savez(os.path.join(ckpt_dir, "offload_blocks.npz"),
                     **engine._block_opt.state_dict())
        meta = {
            "tag": str(tag),
            "global_steps": engine.global_steps,
            "global_samples": engine.global_samples,
            "micro_steps": engine.micro_steps,
            "skipped_steps": engine.skipped_steps,
            "dp_world_size": engine.topology.data_parallel_size,
            "mesh": engine.topology.axis_sizes,
            "zero_stage": engine.zero_stage,
            "dtype": engine._config.precision_dtype,
            "lr_scheduler": (engine.lr_scheduler.state_dict()
                             if engine.lr_scheduler else None),
            "client_state": client_state or {},
        }
        if jax.process_index() == 0:
            with open(os.path.join(ckpt_dir, "ds_meta.json"), "w") as f:
                json.dump(meta, f, indent=2)
            if save_latest:
                with open(os.path.join(save_dir, "latest"), "w") as f:
                    f.write(str(tag))
        from .. import comm as dist

        dist.barrier("checkpoint_save")
        log_dist(f"saved checkpoint {ckpt_dir}", ranks=[0])
        return ckpt_dir

    def load(self, load_dir: str, tag: Optional[str] = None,
             load_optimizer_states: bool = True, load_module_only: bool = False):
        engine = self.engine
        load_dir = self._default_dir(load_dir, for_load=True)
        if tag is None:
            latest_path = os.path.join(load_dir, "latest")
            if not os.path.isfile(latest_path):
                logger.warning(f"no 'latest' file at {load_dir}; nothing loaded")
                return None, {}
            with open(latest_path) as f:
                tag = f.read().strip()
        ckpt_dir = os.path.join(load_dir, str(tag))
        meta_path = os.path.join(ckpt_dir, "ds_meta.json")
        meta: Dict[str, Any] = {}
        if os.path.isfile(meta_path):
            with open(meta_path) as f:
                meta = json.load(f)

        backend = self._backend("load")
        # abstract target carries *current* shardings -> orbax reshards on read
        abstract = jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            engine.state, engine.state_shardings)
        if load_module_only or not load_optimizer_states:
            loaded = backend.load(os.path.join(ckpt_dir, "state"),
                                  abstract_target=abstract)
            engine.state["params"] = loaded["params"]
            if not load_module_only:
                engine.state["step"] = loaded["step"]
                engine.state["scaler"] = loaded["scaler"]
        else:
            engine.state = backend.load(os.path.join(ckpt_dir, "state"),
                                        abstract_target=abstract)

        if getattr(engine, "_offload_opt", None) is not None:
            # re-sync this process's host master partition with the restored
            # params (grad/ZeRO-partition layout), then overlay saved
            # moments/master when present
            partitioned = engine.to_grad_layout(engine.state["params"])
            pieces = engine._offload_pieces_of(partitioned)
            for piece, off, size in zip(pieces,
                                        engine._offload_opt.offsets[:-1],
                                        engine._offload_opt.sizes):
                engine._offload_opt.master[off:off + size] = \
                    np.asarray(piece, np.float32).reshape(-1)
            off_path = os.path.join(
                ckpt_dir, f"offload_optimizer.p{jax.process_index()}.npz")
            if not os.path.isfile(off_path) and jax.process_count() == 1:
                # round-1 checkpoints used the unsuffixed name
                off_path = os.path.join(ckpt_dir, "offload_optimizer.npz")
            if load_optimizer_states and not load_module_only and \
                    os.path.isfile(off_path):
                with np.load(off_path) as z:
                    engine._offload_opt.load_state_dict(dict(z))

        if getattr(engine, "_block_opt", None) is not None:
            blk_path = os.path.join(ckpt_dir, "offload_blocks.npz")
            if os.path.isfile(blk_path):
                with np.load(blk_path) as z:
                    sd = dict(z)
                if load_optimizer_states and not load_module_only:
                    engine._block_opt.load_state_dict(sd)
                else:
                    # module-only load: restore the master weights but keep
                    # fresh moments/step counts (matches the resident gating)
                    engine._block_opt.master[:] = sd["master"]
                engine._param_store.master = engine._block_opt.param_leaves()
                engine._param_store.refresh_compute()

        engine.global_steps = int(meta.get("global_steps", 0))
        engine.global_samples = int(meta.get("global_samples", 0))
        engine.micro_steps = int(meta.get("micro_steps", 0))
        engine.skipped_steps = int(meta.get("skipped_steps", 0))
        if engine.lr_scheduler is not None and meta.get("lr_scheduler"):
            engine.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        log_dist(f"loaded checkpoint {ckpt_dir} at step {engine.global_steps}",
                 ranks=[0])
        return ckpt_dir, meta.get("client_state", {})
