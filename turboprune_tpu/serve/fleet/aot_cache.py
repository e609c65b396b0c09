"""Persistent on-disk cache of serialized AOT executables.

A fleet restart would otherwise re-compile every (model, bucket) pair.
After ``jit(...).lower(...).compile()`` the compiled executable is
serialized with ``jax.experimental.serialize_executable`` (payload + in/out
pytree defs + the ids of the devices it was compiled for) and written to
one file per key; a later process deserializes it ONTO THOSE DEVICES and
serves without invoking the compiler. It is separate from XLA's own
persistent compilation cache (utils/compile_cache.py): its key is the
serving vocabulary — plan signature x bucket — which the exec manifest
audits.

Keying: the filename hash covers the semantic identity of the computation —
HLO fingerprint (sha256 of the lowered StableHLO text), the execution-plan
signature (compacted widths / N:M plan digest / masked), and the batch
bucket. The environment identity (jax, jaxlib, backend) is stored in the
entry's metadata and CHECKED at load: a mismatch — or an entry compiled for
devices this process does not have — is a "bypass" (the entry is ignored
and later overwritten by the current environment's store), never a crash
and never a silent wrong-executable hit. Unreadable or truncated entries
are quarantined (renamed ``*.quarantined``) and counted, so one corrupt
file degrades to a single cold compile instead of taking the process down.

Writes are atomic (tmp file + rename) so concurrent replicas sharing a
cache directory never observe torn entries.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
from pathlib import Path
from typing import Any, Optional

import jax

_FORMAT_VERSION = 2  # 2: entries carry the executable's device ids
_SUFFIX = ".aotx"

# Load statuses (also the counter keys, exported via stats()).
HIT = "hit"
MISS = "miss"
BYPASS = "bypass"
CORRUPT = "corrupt"


def _env_meta() -> dict:
    import jaxlib

    return {
        "format": _FORMAT_VERSION,
        "jax": jax.__version__,
        "jaxlib": getattr(jaxlib, "__version__", "unknown"),
        "backend": jax.default_backend(),
    }


class AOTExecutableCache:
    """Directory of serialized executables; thread-safe, shared fleet-wide."""

    def __init__(self, cache_dir: str | Path):
        self.dir = Path(cache_dir)
        self.dir.mkdir(parents=True, exist_ok=True)
        self._lock = threading.Lock()
        # guarded-by: _lock
        self._counters = {HIT: 0, MISS: 0, BYPASS: 0, CORRUPT: 0, "stores": 0}
        # guarded-by: _lock. Ledger of every key minted this process:
        # key -> {"plan_kind", "bucket"}. The audit surface for the exec
        # manifest — tests assert each on-disk *.aotx key traces back to a
        # (plan kind, bucket) pair the static manifest covers.
        self._key_meta: dict = {}

    # --------------------------------------------------------------- keying
    @staticmethod
    def fingerprint(lowered) -> str:
        """HLO fingerprint of a ``jax.jit(...).lower(...)`` result."""
        return hashlib.sha256(lowered.as_text().encode()).hexdigest()

    def make_key(
        self,
        *,
        hlo_fingerprint: str,
        plan_signature: Any = ("masked",),
        bucket: int = 0,
    ) -> str:
        blob = json.dumps(
            {
                "hlo": hlo_fingerprint,
                "plan": repr(plan_signature),
                "bucket": int(bucket),
            },
            sort_keys=True,
        )
        key = hashlib.sha256(blob.encode()).hexdigest()[:40]
        kind = (
            str(plan_signature[0])
            if isinstance(plan_signature, (tuple, list)) and plan_signature
            else repr(plan_signature)
        )
        with self._lock:
            self._key_meta[key] = {"plan_kind": kind, "bucket": int(bucket)}
        return key

    def key_meta(self) -> dict:
        """Snapshot of the key ledger: key -> {plan_kind, bucket} for every
        key minted via make_key this process."""
        with self._lock:
            return {k: dict(v) for k, v in self._key_meta.items()}

    def _path(self, key: str) -> Path:
        return self.dir / f"{key}{_SUFFIX}"

    # ---------------------------------------------------------------- load
    def load(self, key: str):
        """Returns ``(compiled_or_None, status)`` with status one of
        hit/miss/bypass/corrupt. Never raises on a bad entry."""
        path = self._path(key)
        if not path.exists():
            return None, self._count(MISS)
        try:
            entry = pickle.loads(path.read_bytes())
            meta = entry["meta"]
        # graftlint: disable=broad-except -- degrade-don't-die: any unreadable/truncated/hostile entry must quarantine to a cold compile, not crash the serving process
        except Exception:
            self._quarantine(path)
            return None, self._count(CORRUPT)
        env = _env_meta()
        if any(meta.get(k) != env[k] for k in env):
            # Built by a different jax/jaxlib/backend — executables are not
            # portable across those, so ignore it; the caller compiles and
            # store() overwrites with the current environment's build.
            return None, self._count(BYPASS)
        by_id = {d.id: d for d in jax.devices()}
        if any(i not in by_id for i in entry["devices"]):
            # Compiled for a device this process does not have (another
            # topology sharing the directory): not ours to load.
            return None, self._count(BYPASS)
        from jax.experimental import serialize_executable

        try:
            # execution_devices defaults to EVERY device of the backend; a
            # one-device executable loaded that way dies at its first call
            # on any multi-device host.
            compiled = serialize_executable.deserialize_and_load(
                entry["payload"],
                entry["in_tree"],
                entry["out_tree"],
                execution_devices=[by_id[i] for i in entry["devices"]],
            )
        except (jax.errors.JaxRuntimeError, pickle.UnpicklingError, EOFError):
            # A payload the runtime refuses (truncated, built for other CPU
            # features) degrades to a compile. Anything else — a changed
            # signature, say — is this module's bug and must raise.
            self._quarantine(path)
            return None, self._count(CORRUPT)
        return compiled, self._count(HIT)

    # --------------------------------------------------------------- store
    def store(self, key: str, compiled) -> bool:
        """Serialize + atomically write; returns False (counted nowhere
        fatal) when the executable refuses to serialize."""
        try:
            from jax.experimental import serialize_executable

            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled
            )
        # graftlint: disable=broad-except -- degrade-don't-die: an unserializable executable just means this entry stays cold; serving correctness is unaffected
        except Exception:
            with self._lock:
                self._counters["store_failed"] = (
                    self._counters.get("store_failed", 0) + 1
                )
            return False
        entry = {
            "meta": _env_meta(),
            "devices": [
                d.id for d in compiled.runtime_executable().local_devices()
            ],
            "payload": payload,
            "in_tree": in_tree,
            "out_tree": out_tree,
        }
        path = self._path(key)
        tmp = path.with_suffix(f".tmp.{os.getpid()}.{threading.get_ident()}")
        tmp.write_bytes(pickle.dumps(entry))
        os.replace(tmp, path)
        with self._lock:
            self._counters["stores"] += 1
        return True

    # ------------------------------------------------------------ plumbing
    def _count(self, status: str) -> str:
        with self._lock:
            self._counters[status] = self._counters.get(status, 0) + 1
        return status

    def _quarantine(self, path: Path) -> None:
        try:
            os.replace(path, path.with_suffix(".quarantined"))
        except OSError:
            pass  # already moved by a racing loader, or dir went away

    def stats(self) -> dict:
        with self._lock:
            out = dict(self._counters)
        out["entries"] = len(list(self.dir.glob(f"*{_SUFFIX}")))
        out["quarantined"] = len(list(self.dir.glob("*.quarantined")))
        out["dir"] = str(self.dir)
        return out


def open_cache(cache_dir: str | Path | None) -> Optional[AOTExecutableCache]:
    """'' / None disables the persistent layer (in-memory buckets only)."""
    if not cache_dir:
        return None
    return AOTExecutableCache(cache_dir)
