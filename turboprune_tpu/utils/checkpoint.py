"""Orbax checkpoints with the reference's artifact roles.

The reference persists five artifact roles with torch.save
(/root/reference/run_experiment.py:82-123,
standard_pruning_harness.py:190-223, harness_utils.py:354-365):

  checkpoints/model_init          level-0 starting weights (imp rewind target)
  checkpoints/model_rewind        weights at rewind_epoch of level 0 (wr target)
  artifacts/optimizer_init        optimizer state at level 0 start
  artifacts/optimizer_rewind      optimizer state at rewind_epoch
  checkpoints/model_level_{L}     end-of-level weights (next level's input)

Here a "model" checkpoint is the ``{params, masks, batch_stats}`` pytree
(the reference's state_dict carries mask buffers and BN running stats the
same way) and an "optimizer" checkpoint is the optax ``opt_state`` pytree.
Rewind semantics (reference PruneModel.reset_weights,
custom_models.py:112-146): imp -> restore params+batch_stats from init,
wr -> from rewind, lrr / at_init -> keep trained weights; masks are NEVER
restored — the freshly pruned masks always survive a rewind.

The directory is for processes that do not hold the state: a resumed run and
the server. A continuous run reads nothing back from it. The level loop hands
its state on in memory (driver.run), and the rewind targets are RESIDENT: the
process that saves the target its run rewinds to (``rewind_roles``) keeps the
host tree it fetched for the save, and every later rewind takes it from there.
A process that did not write it (a resume) reads it from disk once, at first
use, and keeps it as a host tree too. A rewind's host-to-device copy is the
``replicate`` of the level's set-up (``level/setup``), not the rewind's own.

Because the loop reads nothing back, a LEVEL save writes behind the next
level's training (``ExperimentCheckpoints``): ``save_level`` brings the tree
to the host and returns, the epoch loop hands it to the one writer thread once
the next epoch is dispatched, and ``wait()`` stands before every read of the
directory, the next save and the run's end. A level is on disk at the next
``wait()``, not when ``save_level`` returns; Orbax commits a directory by
rename, so until then it is not there at all.
"""

from __future__ import annotations

import json
import os
import re
import shutil
from concurrent.futures import Future, ThreadPoolExecutor
from pathlib import Path
from typing import Any, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp

from . import tracing

PyTree = Any

MODEL_INIT = "model_init"
MODEL_REWIND = "model_rewind"
OPTIMIZER_INIT = "optimizer_init"
OPTIMIZER_REWIND = "optimizer_rewind"
MID_LEVEL = "mid_level"
# training_type -> the model role a post-prune rewind restores, and what of
# it: never the masks.
_REWIND_ROLE = {"imp": MODEL_INIT, "wr": MODEL_REWIND}
_REWOUND = ("params", "batch_stats")


def rewind_roles(training_type: str, rewind_optimizer: bool = False) -> frozenset:
    """The roles a run of this training type rewinds to: what the process
    that saves them keeps (``ExperimentCheckpoints(keep=...)``)."""
    roles = {_REWIND_ROLE.get(training_type), OPTIMIZER_REWIND if rewind_optimizer else None}
    return frozenset(roles - {None})

_LEVEL_RE = re.compile(r"^model_level_(\d+)$")


def _primary_only_checkpointer() -> ocp.StandardCheckpointer:
    """A Checkpointer whose internal barriers involve ONLY process 0.

    ocp.StandardCheckpointer.save() unconditionally runs
    sync_global_processes barriers across every process in the world — so a
    save called under ``if is_primary()`` would leave host 0 stuck in
    Orbax's barrier while the other hosts wait at our own sync_hosts().
    MultiprocessingOptions(active_processes={0}) tells Orbax only process 0
    participates, making primary-only save safe."""
    if jax.process_count() == 1:
        return ocp.StandardCheckpointer()
    return ocp.StandardCheckpointer(
        multiprocessing_options=ocp.options.MultiprocessingOptions(
            primary_host=0,
            active_processes={0},
            barrier_sync_key_prefix="tpk_primary_save",
        )
    )


def fetch_to_host(tree: PyTree) -> PyTree:
    """Every device leaf as numpy, under a ``ckpt/fetch`` span. device_get
    works per host on replicated arrays; a leaf that is on the host already
    passes through."""
    with tracing.span("ckpt/fetch"):
        return jax.tree.map(
            lambda x: np.asarray(jax.device_get(x)) if isinstance(x, jax.Array) else x,
            tree,
        )


def _write_tree(path: Path, host_tree: PyTree, **attrs) -> None:
    """The primary's write of a tree that is on the host already, under a
    ``ckpt/write`` span: the old directory goes, Orbax writes a temporary one
    and commits it by rename. ``attrs`` are the span's where the caller's
    thread is not this one (the writer of ``ExperimentCheckpoints``)."""
    with tracing.span("ckpt/write", **attrs):
        ckptr = _primary_only_checkpointer()
        if path.exists():
            shutil.rmtree(path)
        ckptr.save(path, host_tree)
        ckptr.wait_until_finished()


def save_pytree(path: str | Path, tree: PyTree) -> None:
    """Atomic directory-style save (overwrites an existing checkpoint).

    Multi-host: PRIMARY-ONLY. Framework state is replicated across hosts
    (params/masks/opt_state all live on every host — see parallel/mesh.py
    ``replicated``), so host 0 materializes the tree as numpy and writes
    alone; everyone else waits at a barrier. N hosts doing rmtree+save on a
    shared filesystem would stomp one directory (the reference's torch.save
    is likewise rank-0-only, standard_pruning_harness.py:190-199).

    REQUIREMENT: on >1 process the experiment dir must be on storage every
    host can read (NFS/GCS/localhost-shared disk) — restore_pytree is called
    by ALL hosts of a RESUMED run (level resume, and its first rewind)."""
    from ..parallel.multihost import is_primary, sync_hosts

    path = Path(path).resolve()
    if is_primary():
        # Saving numpy keeps the array leaves fully addressable for the
        # single-process save. A rewind target comes fetched already, by the
        # caller that keeps it.
        _write_tree(path, fetch_to_host(tree))
    with tracing.span("ckpt/barrier"):
        sync_hosts(f"save_pytree:{path.name}")


def restore_pytree(path: str | Path, like: Optional[PyTree] = None) -> PyTree:
    """Restore; pass ``like`` (a matching concrete/abstract pytree) to get
    exact container types back (optax namedtuples, custom nodes)."""
    path = Path(path).resolve()
    with tracing.span("ckpt/read"):
        ckptr = ocp.StandardCheckpointer()
        if like is None:
            return ckptr.restore(path)
        abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, like)
        return ckptr.restore(path, abstract)


# --- bit-packed mask payloads --------------------------------------------
# Boolean mask trees serialize 1 byte/element; at ResNet50 scale that is
# ~25 MB of masks PER checkpoint role, all of it bits. Masks are packed to
# uint8 bitfields (np.packbits — host-side; the save path materializes
# numpy anyway) with an explicit shape vector per leaf, an 8x smaller
# payload. Checkpoints written before this change carry raw bool masks;
# ``restore_model_tree`` detects which layout is on disk from Orbax's
# _METADATA manifest and reads either, so legacy experiment dirs stay
# loadable.

MASKS_KEY = "masks"
MASKS_PACKED_KEY = "masks_packed"


def _is_none(x) -> bool:
    return x is None


def pack_mask_tree(masks: PyTree) -> PyTree:
    """bool leaves -> {"bits": uint8[ceil(n/8)], "shape": int64[ndim]};
    None leaves (non-prunable positions) pass through."""

    def pack(m):
        if m is None:
            return None
        arr = np.asarray(jax.device_get(m)).astype(bool)
        return {
            "bits": np.packbits(arr.reshape(-1)),
            "shape": np.asarray(arr.shape, np.int64),
        }

    return jax.tree.map(pack, masks, is_leaf=_is_none)


def unpack_mask_tree(packed: PyTree) -> PyTree:
    """Inverse of pack_mask_tree; shapes come from the stored metadata."""

    def unpack(leaf):
        if leaf is None:
            return None
        shape = tuple(int(s) for s in np.asarray(leaf["shape"]))
        n = int(np.prod(shape)) if shape else 1
        bits = np.unpackbits(np.asarray(leaf["bits"]), count=n)
        return bits.astype(bool).reshape(shape)

    def is_packed_leaf(x):
        return x is None or (isinstance(x, dict) and set(x) == {"bits", "shape"})

    return jax.tree.map(unpack, packed, is_leaf=is_packed_leaf)


def packed_mask_like(masks_like: PyTree) -> PyTree:
    """Abstract packed tree (for restore-with-like) from an unpacked
    mask-tree template — shapes are derivable: prod(shape) bits."""

    def like(m):
        if m is None:
            return None
        n = int(np.prod(m.shape)) if m.shape else 1
        return {
            "bits": np.zeros((n + 7) // 8, np.uint8),
            "shape": np.zeros(len(m.shape), np.int64),
        }

    return jax.tree.map(like, masks_like, is_leaf=_is_none)


def _has_packed_masks(path: Path) -> bool:
    """Did this checkpoint serialize masks bit-packed? Read from Orbax's
    _METADATA manifest (tree_metadata keys are stringified key-paths);
    unreadable/absent manifest -> assume the legacy raw-bool layout."""
    try:
        meta = json.loads((Path(path) / "_METADATA").read_text())
    except (OSError, ValueError):
        return False
    keys = meta.get("tree_metadata", {})
    return any(f"'{MASKS_PACKED_KEY}'" in k for k in keys)


def _packed(tree: dict) -> dict:
    """A model-role tree ({"params", "masks", ...extras}) as it is written:
    the mask payload bit-packed under ``masks_packed``."""
    out = dict(tree)
    with tracing.span("ckpt/fetch"):  # the masks come to the host to be packed
        out[MASKS_PACKED_KEY] = pack_mask_tree(out.pop(MASKS_KEY))
    return out


def save_model_tree(path: str | Path, tree: dict) -> None:
    """Save a model-role tree with its masks packed (``_packed``)."""
    save_pytree(path, _packed(tree))


def restore_model_tree(path: str | Path, like: dict) -> dict:
    """Restore a model-role tree against an UNPACKED ``like`` (with a
    "masks" entry), transparently handling both layouts: bit-packed
    (current) and raw bool (legacy checkpoints from before the packing
    change). Returns the unpacked form either way."""
    if not _has_packed_masks(Path(path).resolve()):
        return restore_pytree(path, like)
    plike = dict(like)
    plike[MASKS_PACKED_KEY] = packed_mask_like(plike.pop(MASKS_KEY))
    restored = restore_pytree(path, plike)
    restored[MASKS_KEY] = unpack_mask_tree(restored.pop(MASKS_PACKED_KEY))
    return restored


class _WriteBehind:
    """The one writer thread of a process and the one tree it is given: held
    from ``hold()`` until ``start()`` hands it over, in flight from then until
    ``settle()`` has its outcome. Only the owner's thread touches this object;
    the writer gets its path and its tree as arguments and gives its outcome
    back in a Future. The thread is made by the first hand-over."""

    def __init__(self):
        self._pool: Optional[ThreadPoolExecutor] = None
        self._held: Optional[tuple] = None
        self._in_flight: Optional[Future] = None

    def hold(self, path: Path, host_tree: PyTree, attrs: dict) -> None:
        self._held = (path, host_tree, attrs)

    def start(self) -> None:
        if self._held is None:
            return
        (path, host_tree, attrs), self._held = self._held, None
        if self._pool is None:
            self._pool = ThreadPoolExecutor(1, thread_name_prefix="ckpt-writer")
        self._in_flight = self._pool.submit(_write_tree, path, host_tree, **attrs)

    def settle(self) -> None:
        """Block until the write in flight is committed, under ``ckpt/wait``,
        and raise here what the writer raised; nothing where none is."""
        self.start()
        write, self._in_flight = self._in_flight, None
        if write is not None:
            with tracing.span("ckpt/wait"):
                write.result()


class ExperimentCheckpoints:
    """Role-addressed checkpoints under an experiment directory (the
    reference's checkpoints/ + artifacts/ split, harness_utils.py:90-93).

    A level save writes BEHIND the caller (module docstring): at most one
    level is held or in flight (``_WriteBehind``), on the primary's one
    writer thread from ``start_write()`` on, and ``wait()`` settles it. Every
    method here that reads or lists the directory, and every save, waits
    first; ``driver.run`` waits before it returns or raises. Every process of
    a multi-host run reaches those points in the same order, which is what
    lets ``wait()`` hold the save's cross-host barrier."""

    def __init__(self, expt_dir: str | Path, keep: frozenset = frozenset()):
        self.expt_dir = Path(expt_dir)
        self.checkpoints_dir = self.expt_dir / "checkpoints"
        self.artifacts_dir = self.expt_dir / "artifacts"
        self.checkpoints_dir.mkdir(parents=True, exist_ok=True)
        self.artifacts_dir.mkdir(parents=True, exist_ok=True)
        # The rewind targets this process holds, as host trees by role (module
        # docstring): those of ``keep`` from the moment they are saved, any
        # other from its first use. Host memory, not HBM, and never handed to
        # a step: the step donates its state, and ``replicate`` would alias a
        # device tree, so a rewind gives the state a fresh device copy of a
        # numpy one (``replicate`` in ``setup_level``).
        self._keep = frozenset(keep)
        self._resident: dict[str, PyTree] = {}
        # The level save that ``wait()`` has yet to settle: its directory's
        # name, on every process (the barrier's key). Its tree and its write
        # are the primary's alone: a process that never saved a level (the
        # server) or is not the primary never starts the writer's thread.
        self._unsettled: Optional[str] = None
        self._behind = _WriteBehind()

    # --- path helpers -----------------------------------------------------
    def model_path(self, role: str) -> Path:
        return self.checkpoints_dir / role

    def optimizer_path(self, role: str) -> Path:
        return self.artifacts_dir / role

    def level_path(self, level: int) -> Path:
        return self.checkpoints_dir / f"model_level_{level}"

    # --- model roles ------------------------------------------------------
    def model_state(self, state) -> dict:
        return {
            "params": state.params,
            "masks": state.masks,
            "batch_stats": state.batch_stats,
        }

    # --- the write behind -------------------------------------------------
    def start_write(self) -> None:
        """Hand the level that ``save_level`` fetched to the writer; nothing
        where none is held. The epoch loop calls this once an epoch is
        dispatched and the host is about to stand waiting for the device: a
        write begun at once would share the host with the next level's prune
        and set-up and stretch both (PERF.md, PR 30)."""
        self._behind.start()

    def wait(self) -> None:
        """Settle the level save in flight, if there is one: start its write
        if nobody has, block until its directory is committed (``ckpt/wait``:
        the seconds a caller stood waiting for a write, about 0 where the
        write was hidden), re-raise here what the writer raised, then hold
        the barrier after which every host may read it."""
        from ..parallel.multihost import sync_hosts

        if self._unsettled is None:
            return
        name, self._unsettled = self._unsettled, None
        self._behind.settle()
        with tracing.span("ckpt/barrier"):
            sync_hosts(f"save_pytree:{name}")

    def save_model(self, role: str, state) -> None:
        self.wait()
        tree = self.model_state(state)
        if role in self._keep:
            # On EVERY process: each keeps its own copy of the replicated
            # state. The primary's save below finds the fetch done.
            tree = fetch_to_host(tree)
            self._resident[role] = {k: tree[k] for k in _REWOUND}
        save_model_tree(self.model_path(role), tree)

    def load_model(self, role: str, like_state) -> dict:
        self.wait()
        return restore_model_tree(
            self.model_path(role), self.model_state(like_state)
        )

    def save_level(self, level: int, state) -> None:
        """Bring the level's tree to the host and return: the write runs
        behind the caller, from ``start_write()`` to ``wait()``. The fetch
        cannot: the next level's first step donates the state's buffers."""
        from ..parallel.multihost import is_primary

        self.wait()
        path = self.level_path(level).resolve()
        tree = _packed(self.model_state(state))
        self._unsettled = path.name
        if is_primary():
            # The writer's span stack is its own thread's, so the span there
            # is told the level (and epoch) it would have inherited here.
            self._behind.hold(path, fetch_to_host(tree), tracing.inherited())

    def load_level(self, level: int, like_state) -> dict:
        self.wait()
        return restore_model_tree(
            self.level_path(level), self.model_state(like_state)
        )

    def has_model(self, role: str) -> bool:
        self.wait()
        return self.model_path(role).exists()

    def has_level(self, level: int) -> bool:
        self.wait()
        return self.level_path(level).exists()

    def saved_levels(self) -> list[int]:
        """The committed levels: one whose write has not been renamed into
        place has no ``model_level_<n>`` directory yet."""
        self.wait()
        out = []
        for p in self.checkpoints_dir.iterdir():
            m = _LEVEL_RE.match(p.name)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    # --- mid-level (epoch-granular) role ----------------------------------
    # Beyond-reference: the reference can only resume at level granularity
    # (a preemption at epoch 85/90 replays the whole level). On preemptible
    # TPUs epoch-granular re-entry is the robustness feature that actually
    # matters (SURVEY.md §5), so one rotating slot holds the FULL train
    # state (params/masks/batch_stats/opt_state/step) plus a tiny JSON
    # header that can be peeked without deserializing the state.

    def mid_level_path(self) -> Path:
        return self.checkpoints_dir / MID_LEVEL

    def _mid_level_meta_path(self) -> Path:
        return self.checkpoints_dir / "mid_level_meta.json"

    def save_mid_level(self, level: int, epoch: int, state, meta: dict) -> None:
        import json

        from ..parallel.multihost import is_primary, sync_hosts

        # The (level, epoch) tag is stored in BOTH the (atomically-written)
        # Orbax tree and the JSON header. A preemption between the two
        # writes leaves them disagreeing; load_mid_level detects that and
        # the harness falls back to replaying the level — never a mixed
        # old-header/new-state restore.
        tag = level * 1_000_000 + epoch  # int: Orbax round-trips it exactly
        # In line, unlike a level save: the header below may only follow a
        # committed tree.
        self.wait()
        save_model_tree(
            self.mid_level_path(),
            {
                "params": state.params,
                "masks": state.masks,
                "batch_stats": state.batch_stats,
                "opt_state": state.opt_state,
                "step": state.step,
                "tag": tag,
            },
        )
        if is_primary():
            p = self._mid_level_meta_path()
            tmp = p.with_suffix(".tmp")  # atomic: no truncated JSON on crash
            tmp.write_text(json.dumps({"level": level, "epoch": epoch, **meta}))
            tmp.replace(p)
        sync_hosts("mid_level_meta")

    def peek_mid_level(self) -> Optional[dict]:
        """Header {level, epoch, ...} or None — no state deserialization.
        The header may be one save older than the state tree (see
        save_mid_level); load_mid_level is the consistency authority."""
        import json

        p = self._mid_level_meta_path()
        if not p.exists() or not self.mid_level_path().exists():
            return None
        try:
            return json.loads(p.read_text())
        except (ValueError, OSError):
            return None

    def load_mid_level(self, like_state, expect_level: int, expect_epoch: int):
        """Restore the slot; returns the state dict, or None when the slot's
        embedded tag disagrees with the header-derived expectation (a torn
        save — the caller must replay the level from its start)."""
        self.wait()
        restored = restore_model_tree(
            self.mid_level_path(),
            {
                "params": like_state.params,
                "masks": like_state.masks,
                "batch_stats": like_state.batch_stats,
                "opt_state": like_state.opt_state,
                "step": like_state.step,
                "tag": 0,
            },
        )
        if int(restored.pop("tag")) != expect_level * 1_000_000 + expect_epoch:
            return None
        return restored

    # Stream-position loaders (grain): each host's iterator state is ITS
    # OWN shard position, so blobs are per-host files (unique paths — no
    # cross-host write conflict, unlike the shared JSON header which is
    # primary-only and would silently hand every host the primary's
    # position). An 8-byte (level, epoch) tag prefixes the blob so a
    # preemption between the state save and the stream write cannot pair a
    # stale stream with a newer state — the loader falls back to a fresh
    # pass instead.

    def _mid_level_stream_path(self, pid: int) -> Path:
        return self.checkpoints_dir / f"mid_level_stream_{pid}"

    def save_mid_level_stream(
        self, level: int, epoch: int, blob: bytes, pid: int
    ) -> None:
        tag = (level * 1_000_000 + epoch).to_bytes(8, "big")
        p = self._mid_level_stream_path(pid)
        tmp = p.with_suffix(".tmp")
        tmp.write_bytes(tag + blob)
        tmp.replace(p)

    def load_mid_level_stream(
        self, level: int, epoch: int, pid: int
    ) -> Optional[bytes]:
        """The blob, or None when absent / tagged for a different save."""
        p = self._mid_level_stream_path(pid)
        if not p.exists():
            return None
        raw = p.read_bytes()
        if len(raw) < 8 or int.from_bytes(raw[:8], "big") != (
            level * 1_000_000 + epoch
        ):
            return None
        return raw[8:]

    def clear_mid_level(self) -> None:
        """Drop the slot (primary-only). Called whenever training reaches a
        level the slot does not belong to: levels run in ascending order, so
        a non-matching slot is always from an abandoned trajectory and would
        otherwise hijack a later re-run of its level (e.g. resume at level 2
        after a preemption at level 3 — the recomputed level-3 entry must
        not restore the old trajectory's state)."""
        from ..parallel.multihost import is_primary, sync_hosts

        if is_primary():
            self._mid_level_meta_path().unlink(missing_ok=True)
            if self.mid_level_path().exists():
                shutil.rmtree(self.mid_level_path())
            for p in self.checkpoints_dir.glob("mid_level_stream_*"):
                p.unlink(missing_ok=True)
        sync_hosts("mid_level_clear")

    # --- optimizer roles --------------------------------------------------
    def save_optimizer(self, role: str, opt_state) -> None:
        self.wait()
        if role in self._keep:  # optimizer_rewind, like a model rewind target
            opt_state = self._resident[role] = fetch_to_host(opt_state)
        save_pytree(self.optimizer_path(role), opt_state)

    def load_optimizer(self, role: str, like_opt_state):
        self.wait()
        return restore_pytree(self.optimizer_path(role), like_opt_state)

    # --- resident rewind targets -------------------------------------------
    def _held(self, role: str, read) -> PyTree:
        """``role``'s rewind target as a host tree: resident, or ``read`` from
        disk this once, fetched (Orbax restores onto the devices of the state
        it is shown) and kept."""
        if role not in self._resident:
            self._resident[role] = fetch_to_host(read())
        return self._resident[role]

    def rewind_model(self, role: str, like_state) -> dict:
        """``{"params", "batch_stats"}`` of ``model_init`` / ``model_rewind``.
        The open span (``level/rewind``) learns where they came from."""
        tracing.note(source="resident" if role in self._resident else "disk")

        def read():
            restored = self.load_model(role, like_state)
            return {k: restored[k] for k in _REWOUND}

        return self._held(role, read)

    def rewind_optimizer(self, like_opt_state):
        """``optimizer_rewind``, likewise."""
        return self._held(
            OPTIMIZER_REWIND, lambda: self.load_optimizer(OPTIMIZER_REWIND, like_opt_state)
        )


def reset_weights(training_type: str, state, ckpts: ExperimentCheckpoints):
    """Post-prune rewind (reference reset_weights semantics,
    custom_models.py:112-146): restores params + batch_stats from the role'd
    rewind target (the resident host tree, ``ExperimentCheckpoints.rewind_model``;
    the leaves reach the device with the level's ``replicate``, in
    ``level/setup``), KEEPS the current (just-pruned) masks.

      imp      -> model_init
      wr       -> model_rewind
      lrr      -> no-op (learning-rate rewinding keeps trained weights)
      at_init  -> no-op (PaI never rewinds)
    """
    role = _REWIND_ROLE.get(training_type)
    if role is None:
        return state
    target = ckpts.rewind_model(role, state)
    return state.replace(params=target["params"], batch_stats=target["batch_stats"])
