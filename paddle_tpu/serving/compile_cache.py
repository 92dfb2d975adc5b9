"""Persistent AOT program cache for serving engines (ISSUE 14).

A restarted worker process pays the full bucket-grid compile storm
before its first token unless something remembers the executables. The
ProgramCache keys are already canonical — ("decode", B, P, kv_dtype,
wq, ("tp", tp)) names one program completely for one engine geometry —
so this module serializes each LAUNCHED program's compiled XLA
executable to disk under that key and hands it back to the next
process holding the same geometry:

* **save**: for every launched program (its first call recorded the
  argument avals), re-lower AOT (`fn.lower(*avals).compile()`) and
  write `pickle(jax.experimental.serialize_executable.serialize(...))`
  to one file per key;
* **load**: on a ProgramCache miss, look the key up on disk; a hit
  skips BOTH jax tracing and XLA compilation (deserialize + call);
* **reject, never crash**: a corrupt file (bad magic/version/checksum/
  truncation), a fingerprint mismatch (different jax/jaxlib/backend/
  device topology/model geometry), or an executable that fails its
  first call degrades to a counted recompile — a worker must reach
  first-token on a damaged cache directory, just slower.

Entry format (one file per key, name = sha1(key repr)):

    line 1: header JSON {magic, format, fingerprint, key, body_sha256,
            body_len, saved_unix, devices}
    rest:   the pickled (payload, in_tree, out_tree) triple

The fingerprint folds in jax/jaxlib versions, backend, device kind and
count, plus whatever the owner passes as `extra` — the engine passes
its model geometry/state signature, so an engine with different
weights' SHAPES can never adopt a stale executable (same-shape weight
VALUES are call-time arguments, not baked into the executable).

Counters {hits, misses, rejects, saved} surface through the engine's
ServingMetrics as `compile_cache_*` (auto-exposed by the drift-tested
Prometheus registry). Fault point `cache.corrupt_entry` flips bytes of
an entry body at read time — the checksum-reject path, proven in the
soak.

Importable without jax: jax and the serializer load lazily inside
save/load.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
import time
from typing import Dict, Optional

from ..utils import faults

__all__ = ["CompileCache", "cache_fingerprint", "FORMAT_VERSION",
           "FAULT_CORRUPT"]

MAGIC = "PTCC"
FORMAT_VERSION = 2      # 2: the header names the execution devices

# Fires in _read_entry with the raw body in hand: a payload means "the
# disk lied" — bytes are flipped BEFORE checksum verification, so the
# reject path (not a crash) is what the firing proves.
FAULT_CORRUPT = faults.register_point("cache.corrupt_entry")


def cache_fingerprint(extra: Optional[str] = None) -> str:
    """Environment fingerprint an executable is only valid under:
    jax/jaxlib versions, backend, device kind x count — plus the
    owner's `extra` (model geometry). Serialized executables embed
    backend-specific code; running one under any other combination is
    undefined, so a mismatch REJECTS to recompile."""
    import jax
    import jaxlib
    devs = jax.devices()
    parts = [f"jax={jax.__version__}", f"jaxlib={jaxlib.__version__}",
             f"backend={jax.default_backend()}",
             f"devices={len(devs)}x{devs[0].device_kind if devs else '?'}"]
    if extra:
        parts.append(f"extra={extra}")
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:32]


class CompileCache:
    """One on-disk executable store for one engine geometry.

    `path` is the cache directory (created on demand). `extra` joins
    the environment fingerprint — pass the model/engine geometry
    signature so two engines with different models never share a
    directory's entries even if their ProgramCache keys collide.
    """

    def __init__(self, path: str, *, extra: Optional[str] = None):
        self.path = str(path)
        self._extra = extra
        self._fingerprint: Optional[str] = None
        self.counters: Dict[str, int] = {
            "hits": 0, "misses": 0, "rejects": 0, "saved": 0}
        # keys whose entry was rejected this process (corrupt body,
        # stale payload, first-call failure): save_all REWRITES these
        # even when the on-disk header still looks valid — otherwise a
        # damaged-body entry would defeat the warm-restart contract
        # for its key on every future restart
        self.rejected_keys: set = set()

    def fingerprint(self) -> str:
        if self._fingerprint is None:
            self._fingerprint = cache_fingerprint(self._extra)
        return self._fingerprint

    # ---- paths -----------------------------------------------------------
    def entry_path(self, key: tuple) -> str:
        name = hashlib.sha1(repr(key).encode()).hexdigest()
        return os.path.join(self.path, f"{name}.ptcc")

    def keys_on_disk(self):
        """Key reprs of every readable entry (diagnostics/tests)."""
        out = []
        if not os.path.isdir(self.path):
            return out
        for fn in sorted(os.listdir(self.path)):
            if not fn.endswith(".ptcc"):
                continue
            try:
                with open(os.path.join(self.path, fn), "rb") as f:
                    out.append(json.loads(f.readline())["key"])
            except Exception:                             # noqa: BLE001
                continue
        return out

    # ---- write -----------------------------------------------------------
    def save_entry(self, key: tuple, compiled) -> bool:
        """Serialize one AOT-compiled program under `key` (atomic
        rename; concurrent writers of the same key are last-wins with
        either side's complete file). Returns False when this jax
        build cannot serialize executables."""
        try:
            from jax.experimental.serialize_executable import serialize
            body = pickle.dumps(serialize(compiled))
        except Exception:                                 # noqa: BLE001
            return False
        header = {"magic": MAGIC, "format": FORMAT_VERSION,
                  "fingerprint": self.fingerprint(), "key": repr(key),
                  "body_sha256": hashlib.sha256(body).hexdigest(),
                  "body_len": len(body), "saved_unix": int(time.time()),
                  # the devices the program runs on, in assignment
                  # order: a load must name them, or the installed jax
                  # spreads the executable over EVERY local device
                  "devices": [d.id for d in
                              compiled.runtime_executable().local_devices()]}
        os.makedirs(self.path, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=self.path, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(json.dumps(header).encode() + b"\n")
                f.write(body)
            os.replace(tmp, self.entry_path(key))
        except Exception:                                 # noqa: BLE001
            try:
                os.remove(tmp)
            except OSError:
                pass
            return False
        self.counters["saved"] += 1
        self.rejected_keys.discard(key)
        return True

    def save_all(self, program_cache) -> int:
        """Persist every launched program the ProgramCache holds that
        is (a) AOT-lowerable (jit-built, launched at least once so its
        arg avals were recorded) and (b) not already on disk under the
        current fingerprint. Returns entries written. Re-lowering is a
        second compile per NEW entry — drain/shutdown-time cost, never
        on the serving path."""
        written = 0
        for key in program_cache.keys():
            prog = program_cache._programs[key]
            fn = getattr(prog, "fn", prog)
            avals = getattr(prog, "arg_avals", None)
            if avals is None or not hasattr(fn, "lower"):
                continue   # never launched, or loaded-from-disk already
            if key not in self.rejected_keys and \
                    self._header_ok(self.entry_path(key)):
                continue
            try:
                compiled = prog.lower().compile()
            except Exception:                             # noqa: BLE001
                continue
            if self.save_entry(key, compiled):
                written += 1
        return written

    # ---- read ------------------------------------------------------------
    def _header_ok(self, path: str) -> bool:
        """Cheap staleness probe: does a valid-looking entry under the
        CURRENT fingerprint exist at `path`? (save_all's skip test —
        full validation happens at load.)"""
        try:
            with open(path, "rb") as f:
                h = json.loads(f.readline())
            return (h.get("magic") == MAGIC
                    and h.get("format") == FORMAT_VERSION
                    and h.get("fingerprint") == self.fingerprint())
        except Exception:                                 # noqa: BLE001
            return False

    def _read_entry(self, key: tuple):
        """Validate and unpickle one entry; raises ValueError naming
        the defect on any mismatch (the caller counts a reject)."""
        path = self.entry_path(key)
        with open(path, "rb") as f:
            header_line = f.readline()
            body = f.read()
        if faults.fire(FAULT_CORRUPT) is not None and body:
            body = bytes([body[0] ^ 0xFF]) + body[1:]
        try:
            h = json.loads(header_line)
        except Exception as e:                            # noqa: BLE001
            raise ValueError(f"unreadable header: {e}") from e
        if h.get("magic") != MAGIC:
            raise ValueError(f"bad magic {h.get('magic')!r}")
        if h.get("format") != FORMAT_VERSION:
            raise ValueError(f"format {h.get('format')} != "
                             f"{FORMAT_VERSION}")
        if h.get("fingerprint") != self.fingerprint():
            raise ValueError("environment/model fingerprint mismatch")
        if h.get("key") != repr(key):
            raise ValueError("key collision: entry names a different "
                             "program")
        if len(body) != h.get("body_len"):
            raise ValueError(f"truncated body: {len(body)} != "
                             f"{h.get('body_len')}")
        if hashlib.sha256(body).hexdigest() != h.get("body_sha256"):
            raise ValueError("body checksum mismatch")
        return pickle.loads(body) + (h.get("devices"),)

    def load(self, key: tuple):
        """The deserialized executable for `key`, or None (counted as
        hit / miss / reject; every damage class degrades to None — the
        caller recompiles)."""
        path = self.entry_path(key)
        if not os.path.exists(path):
            self.counters["misses"] += 1
            return None
        try:
            payload, in_tree, out_tree, dev_ids = self._read_entry(key)
            import jax
            from jax.experimental.serialize_executable import \
                deserialize_and_load
            by_id = {d.id: d for d in jax.devices()}
            loaded = deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in dev_ids])
        except Exception:                                 # noqa: BLE001
            self.counters["rejects"] += 1
            self.rejected_keys.add(key)
            return None
        self.counters["hits"] += 1
        return loaded

    def __repr__(self):
        return (f"CompileCache({self.path!r}, "
                f"hits={self.counters['hits']}, "
                f"misses={self.counters['misses']}, "
                f"rejects={self.counters['rejects']})")
