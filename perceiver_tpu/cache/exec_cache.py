"""Content-addressed on-disk cache of serialized XLA executables.

Entry anatomy (see docs/SERVING.md "Warm starts" for the operator
view):

- key: sha256 over (jax version, jaxlib version, backend platform +
  device kind + device/process counts, donation layout, hash of the
  lowered StableHLO text). The StableHLO text is the program identity
  — shapes, dtypes, shardings, and donation aliasing are all printed
  there (``analysis/hlo.py`` gates on the same text), so two lowerings
  that could need different executables can never share a key.
- ``<key>.exe``: pickle of ``(payload, in_tree, out_tree)`` from
  ``jax.experimental.serialize_executable.serialize``.
- ``<key>.json``: sidecar with the cost-analysis flops / bytes
  accessed of the lowering (so warm paths skip re-analysis), versions
  (defense in depth against a doctored key), and a label.
- ``<key>.low.json``: a *lowering* record — StableHLO text + derived
  properties for the analysis gates, keyed by target name + source
  digest instead of the text itself (the text is what it caches).

Failure policy: every read path degrades to a miss — a truncated
blob, version skew, json rot, or a concurrently-evicted file all
return ``None`` and count ``stats.corrupt``/``stats.misses``; the
caller then performs the real compile it would have done anyway.
Nothing in here is allowed to raise on a cache problem.

Concurrency: writers serialize to a temp file in the cache directory
and ``os.replace`` it into place — readers see either the whole entry
or no entry, and the last concurrent writer of one key wins with both
executables being equivalent by construction. Eviction tolerates
losing races with other processes' evictions.

Trust: entries are pickles, so the cache directory is code — share it
only within the trust domain that already shares checkpoints.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import re
import tempfile
import threading
import time
from typing import Any, Dict, Optional, Sequence, Tuple

_ENV_VAR = "PERCEIVER_EXEC_CACHE"
_DEFAULT_MAX_BYTES = 4 << 30  # 4 GiB — hundreds of serving buckets

# Host-callback custom calls (jax.debug.print / io_callback /
# pure_callback) reach back into this process.
_CALLBACK_CALL = re.compile(r"custom_call @\S*callback")


def has_host_callbacks(text: str) -> bool:
    """A module with host callbacks must NEVER be served from the
    executable cache: the compiled artifact embeds a host function
    pointer that is garbage in any other process (jax's serializer
    refuses such executables too — this guard just makes the policy
    explicit and skips the doomed serialize)."""
    return _CALLBACK_CALL.search(text) is not None


def topology_fingerprint(backend: Optional[str] = None) -> str:
    """Stable identity of the device world an executable targets:
    platform, device kind, device count, process count. Deliberately
    independent of ``JAX_PLATFORMS`` spelling — two processes that
    resolve to the same backend share keys however they selected it."""
    import jax

    devices = jax.devices(backend)
    kinds = ",".join(sorted({d.device_kind for d in devices}))
    return (f"{devices[0].platform}:{kinds}:d{len(devices)}"
            f":p{jax.process_count()}")


def _versions() -> Tuple[str, str]:
    import jax
    import jaxlib

    return jax.__version__, jaxlib.__version__


_SOURCE_DIGEST: Dict[str, str] = {}


def source_tree_digest(root: Optional[str] = None) -> str:
    """Content hash of every ``.py`` file in the package. Lowering
    records are only valid for the exact code that produced them — a
    one-line model edit must invalidate them, and mtimes lie across
    checkouts, so this hashes contents (a few ms, memoized)."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    root = os.path.abspath(root)
    cached = _SOURCE_DIGEST.get(root)
    if cached is not None:
        return cached
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            h.update(os.path.relpath(path, root).encode())
            with open(path, "rb") as f:
                h.update(hashlib.sha256(f.read()).digest())
    digest = h.hexdigest()[:16]
    _SOURCE_DIGEST[root] = digest
    return digest


@dataclasses.dataclass
class CacheStats:
    """Process-local counters (the serving metrics mirror these)."""

    hits: int = 0
    misses: int = 0
    corrupt: int = 0
    evicted: int = 0
    stores: int = 0
    bytes_read: int = 0
    bytes_written: int = 0

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


class ExecutableCache:
    """One cache directory of serialized executables + lowering
    records, shareable between concurrent processes."""

    # lock discipline (gated by check.py --race): the stats struct's
    # fields are bumped from whichever thread compiles/loads (dotted
    # keys — the struct itself is assigned once in __init__ and never
    # rebound). On-disk state needs no lock here: every write is an
    # atomic tmp+rename, which is the cross-PROCESS discipline.
    _GUARDED = {
        "stats.hits": "_lock",
        "stats.misses": "_lock",
        "stats.corrupt": "_lock",
        "stats.evicted": "_lock",
        "stats.stores": "_lock",
        "stats.bytes_read": "_lock",
        "stats.bytes_written": "_lock",
    }

    def __init__(self, path: str, *,
                 max_bytes: int = _DEFAULT_MAX_BYTES):
        self.path = os.path.abspath(os.path.expanduser(str(path)))
        self.max_bytes = int(max_bytes)
        self.stats = CacheStats()
        self._lock = threading.Lock()
        os.makedirs(self.path, exist_ok=True)

    # -- keys -------------------------------------------------------------

    def executable_key(self, lowered_text: str, *,
                       donate_argnums: Sequence[int] = (),
                       backend: Optional[str] = None,
                       extra: Sequence[Any] = ()) -> str:
        jax_v, jaxlib_v = _versions()
        material = json.dumps({
            "kind": "exe",
            "jax": jax_v,
            "jaxlib": jaxlib_v,
            "topology": topology_fingerprint(backend),
            "donate": sorted(int(i) for i in donate_argnums),
            "hlo": hashlib.sha256(
                lowered_text.encode()).hexdigest(),
            "extra": [str(x) for x in extra],
        }, sort_keys=True)
        return hashlib.sha256(material.encode()).hexdigest()

    def lowering_key(self, name: str, *,
                     backend: Optional[str] = None,
                     extra: Sequence[Any] = ()) -> str:
        """Key for a lowering record: unlike executables the text IS
        the payload, so the key binds the program identity through the
        source tree digest instead."""
        jax_v, jaxlib_v = _versions()
        material = json.dumps({
            "kind": "low",
            "name": name,
            "jax": jax_v,
            "jaxlib": jaxlib_v,
            "topology": topology_fingerprint(backend),
            "source": source_tree_digest(),
            "extra": [str(x) for x in extra],
        }, sort_keys=True)
        return hashlib.sha256(material.encode()).hexdigest()

    # -- paths ------------------------------------------------------------

    def _exe_path(self, key: str) -> str:
        return os.path.join(self.path, f"{key}.exe")

    def _sidecar_path(self, key: str) -> str:
        return os.path.join(self.path, f"{key}.json")

    def _lowering_path(self, key: str) -> str:
        return os.path.join(self.path, f"{key}.low.json")

    # -- atomic write -----------------------------------------------------

    def _write_atomic(self, path: str, data: bytes) -> None:
        fd, tmp = tempfile.mkstemp(dir=self.path, prefix=".tmp-")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(data)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def _drop(self, key: str) -> None:
        for path in (self._exe_path(key), self._sidecar_path(key),
                     self._lowering_path(key)):
            try:
                os.unlink(path)
            except OSError:
                pass

    def _touch(self, *paths: str) -> None:
        # mtime is the LRU clock — a hit must refresh it or steady
        # traffic evicts its own hottest entries
        for path in paths:
            try:
                os.utime(path)
            except OSError:
                pass

    # -- executables ------------------------------------------------------

    def sidecar(self, key: str) -> Optional[dict]:
        try:
            with open(self._sidecar_path(key)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def load_executable(self, key: str):
        """Deserialize the cached executable for ``key``, or None
        (miss). Never raises on a cache problem; counts stats."""
        jax_v, jaxlib_v = _versions()
        side = self.sidecar(key)
        if side is None or not os.path.exists(self._exe_path(key)):
            with self._lock:
                self.stats.misses += 1
            return None
        if side.get("jax") != jax_v or side.get("jaxlib") != jaxlib_v:
            # keys already embed versions, so this only trips on a
            # doctored/collided entry — treat as stale, rebuild
            with self._lock:
                self.stats.misses += 1
            self._drop(key)
            return None
        try:
            with open(self._exe_path(key), "rb") as f:
                blob = f.read()
            payload, in_tree, out_tree = pickle.loads(blob)
            import jax
            from jax.experimental.serialize_executable import (
                deserialize_and_load,
            )

            # the installed jax loads for every device of the backend
            # unless told which ones the executable was compiled for
            by_id = {d.id: d for d in jax.devices()}
            compiled = deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in side["device_ids"]])
        except Exception:
            # truncated/corrupt blob, or an executable this
            # backend/jaxlib cannot load — fall back to a fresh compile
            with self._lock:
                self.stats.corrupt += 1
                self.stats.misses += 1
            self._drop(key)
            return None
        with self._lock:
            self.stats.hits += 1
            self.stats.bytes_read += len(blob)
        self._touch(self._exe_path(key), self._sidecar_path(key))
        return compiled

    def store_executable(self, key: str, compiled, *,
                         sidecar: Optional[dict] = None) -> bool:
        """Serialize + write ``compiled`` under ``key``. Returns False
        (without raising) when the executable does not support
        serialization or the write fails."""
        jax_v, jaxlib_v = _versions()
        try:
            from jax.experimental.serialize_executable import serialize

            payload, in_tree, out_tree = serialize(compiled)
            blob = pickle.dumps((payload, in_tree, out_tree))
        except Exception:
            return False
        meta = {
            "jax": jax_v,
            "jaxlib": jaxlib_v,
            "device_ids": [d.id for d in
                           compiled.runtime_executable().local_devices()],
            "topology": topology_fingerprint(),
            "created": time.time(),
            "payload_bytes": len(blob),
            **(sidecar or {}),
        }
        try:
            self._write_atomic(self._exe_path(key), blob)
            self._write_atomic(
                self._sidecar_path(key),
                json.dumps(meta, sort_keys=True).encode() + b"\n")
        except OSError:
            self._drop(key)
            return False
        with self._lock:
            self.stats.stores += 1
            self.stats.bytes_written += len(blob)
        self._evict()
        return True

    # -- lowering records -------------------------------------------------

    def load_lowering(self, key: str) -> Optional[dict]:
        try:
            with open(self._lowering_path(key)) as f:
                record = json.load(f)
        except (OSError, ValueError):
            with self._lock:
                self.stats.misses += 1
            return None
        if not isinstance(record, dict) or "text" not in record:
            with self._lock:
                self.stats.corrupt += 1
                self.stats.misses += 1
            self._drop(key)
            return None
        with self._lock:
            self.stats.hits += 1
        self._touch(self._lowering_path(key))
        return record

    def store_lowering(self, key: str, record: dict) -> bool:
        try:
            data = json.dumps(record, sort_keys=True).encode() + b"\n"
            self._write_atomic(self._lowering_path(key), data)
        except (OSError, TypeError, ValueError):
            return False
        with self._lock:
            self.stats.stores += 1
            self.stats.bytes_written += len(data)
        self._evict()
        return True

    # -- eviction ---------------------------------------------------------

    def entry_bytes(self) -> int:
        return sum(size for _, _, size in self._entries())

    def _entries(self):
        """[(mtime, key-group paths, bytes)] for every complete-ish
        entry, oldest first. Grouped so an .exe and its sidecar live
        and die together."""
        groups: Dict[str, list] = {}
        try:
            names = os.listdir(self.path)
        except OSError:
            return []
        for name in names:
            if name.startswith(".tmp-"):
                continue
            key = name.split(".", 1)[0]
            groups.setdefault(key, []).append(
                os.path.join(self.path, name))
        out = []
        for key, paths in groups.items():
            mtime, size = 0.0, 0
            for p in paths:
                try:
                    st = os.stat(p)
                except OSError:
                    continue
                mtime = max(mtime, st.st_mtime)
                size += st.st_size
            out.append((mtime, paths, size))
        return sorted(out)

    def _evict(self) -> None:
        """Drop least-recently-used entries until under ``max_bytes``.
        Races with concurrent processes are benign: a lost unlink is
        someone else's eviction."""
        entries = self._entries()
        total = sum(size for _, _, size in entries)
        for _, paths, size in entries:
            if total <= self.max_bytes:
                break
            for p in paths:
                try:
                    os.unlink(p)
                except OSError:
                    pass
            total -= size
            with self._lock:
                self.stats.evicted += 1


# -- the blessed compile sites ------------------------------------------------
# The ``uncached-compile`` lint rule flags raw ``.lower().compile()``
# everywhere outside this package: every AOT compile in the tree is
# supposed to flow through here so it can populate the cache.


def compile_lowered(lowered, *, cache: Optional[ExecutableCache] = None,
                    key: Optional[str] = None,
                    sidecar: Optional[dict] = None):
    """Compile a ``jax.stages.Lowered`` and (best-effort) store the
    result. The raw compile lives here so callers stay cache-honest."""
    compiled = lowered.compile()
    if cache is not None and key:
        cache.store_executable(key, compiled, sidecar=sidecar)
    return compiled


def _cost_summary(stage) -> Dict[str, Optional[float]]:
    """flops / bytes accessed from a Lowered or Compiled cost
    analysis, best-effort (None where the backend exposes none)."""
    try:
        cost = stage.cost_analysis()
    except Exception:
        return {"flops": None, "bytes_accessed": None}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not cost:
        return {"flops": None, "bytes_accessed": None}
    flops = float(cost.get("flops", 0.0)) or None
    accessed = cost.get("bytes accessed")
    return {"flops": flops,
            "bytes_accessed": float(accessed) if accessed is not None
            else None}


# Lowering is serialized process-wide: two lowerings tracing
# CONCURRENTLY can suffix shared private helpers nondeterministically
# (``@_where`` in one module, ``@_where_1`` in the other for the same
# program — observed with two engines warming over one cache dir),
# which forks the text hash and stores duplicate entries. Serial
# lowerings are byte-deterministic, so one lock restores key
# stability; compiles still run in parallel.
_LOWER_LOCK = threading.Lock()


def aot_compile(jitted, args, *, cache: Optional[ExecutableCache] = None,
                donate_argnums: Sequence[int] = (),
                label: str = "", extra_key: Sequence[Any] = (),
                kwargs: Optional[dict] = None):
    """Lower ``jitted`` at ``args`` and return ``(compiled, info)``,
    deserializing from ``cache`` instead of compiling when the key
    hits. ``info``: ``{"hit": bool, "key": str|None, "bytes": int,
    "sidecar": dict|None}`` (``bytes`` = blob read on hit / written on
    miss, 0 without a cache)."""
    with _LOWER_LOCK:
        lowered = jitted.lower(*args, **(kwargs or {}))
        text = None if cache is None else lowered.as_text()
    if cache is None or has_host_callbacks(text):
        # callback-bearing executables embed host pointers — always
        # compile them fresh, never store or load
        return (compile_lowered(lowered),
                {"hit": False, "key": None, "bytes": 0, "sidecar": None})
    key = cache.executable_key(text, donate_argnums=donate_argnums,
                               extra=extra_key)
    compiled = cache.load_executable(key)
    if compiled is not None:
        side = cache.sidecar(key)
        return (compiled,
                {"hit": True, "key": key,
                 "bytes": int((side or {}).get("payload_bytes", 0)),
                 "sidecar": side})
    sidecar = {"label": label, **_cost_summary(lowered)}
    before = cache.stats.bytes_written
    compiled = compile_lowered(lowered, cache=cache, key=key,
                               sidecar=sidecar)
    return (compiled,
            {"hit": False, "key": key,
             "bytes": cache.stats.bytes_written - before,
             "sidecar": sidecar})


# -- process-default cache ----------------------------------------------------

_DEFAULT_CACHES: Dict[str, ExecutableCache] = {}
_DEFAULT_LOCK = threading.Lock()


def default_cache(path: Optional[str] = None
                  ) -> Optional[ExecutableCache]:
    """The process-wide cache: ``path`` if given, else the
    ``PERCEIVER_EXEC_CACHE`` env var, else None (caching off). One
    ``ExecutableCache`` per directory per process, so the engine, the
    trainer, and the predict compat path share stats."""
    path = path or os.environ.get(_ENV_VAR)
    if not path:
        return None
    key = os.path.abspath(os.path.expanduser(path))
    with _DEFAULT_LOCK:
        cache = _DEFAULT_CACHES.get(key)
        if cache is None:
            cache = ExecutableCache(key)
            _DEFAULT_CACHES[key] = cache
        return cache
