"""The persistent compilation cache: serialized executables on disk.

Reference analogue: TVM's compiled-artifact reuse (arxiv 1802.04799) and
the reference stack's one-time graph init amortized across a long
training job — here generalized so EVERY process (CI, serving cold
start, ``fit(resume='auto')``, bench rounds) skips XLA recompilation of
programs that haven't changed.

Layout (default root ``<jax cache dir>/mxtpu-executables`` — see
:func:`jax_cache_dir` — override ``MXTPU_COMPILE_CACHE_DIR``)::

    <root>/<key[:2]>/<key>.bin            # pickled (payload, trees) from
                                          # jax serialize_executable
    <root>/<key[:2]>/<key>.manifest.json  # size + sha256 + metadata

Writes reuse the PR 1 checkpoint plumbing — atomic tmp+fsync+rename via
:func:`~mxnet_tpu.resilience.checkpoint.atomic_write_bytes`, SHA-256
manifests via :func:`~mxnet_tpu.resilience.checkpoint.file_digest` — so
a crash mid-write leaves either the old complete entry or a stray
``.tmp``, never a torn executable. Reads pass the ``compiler.cache.read``
fault site; a corrupt, truncated, or fault-injected entry is quarantined
(deleted) and reported as an *invalidation*, and the caller falls back
to a normal recompile. The cache can only ever cost one recompile —
never a wrong program, never a failed bind.

Size is LRU-bounded (``MXTPU_COMPILE_CACHE_MB``, default 512): hits
touch the entry's mtime; :func:`CompilationCache.evict` drops the
stalest entries until under budget. ``MXTPU_COMPILE_CACHE=0`` disables
the disk layer entirely (the in-process program registry keeps
working). ``compiler.stats()`` mirrors ``retry.stats()``.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Dict, Optional

from ..base import getenv

__all__ = ["CompilationCache", "default_cache", "cache_enabled",
           "cache_stats", "reset_cache_stats", "jax_cache_dir",
           "configure_jax_cache"]

MANIFEST_VERSION = 1

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def jax_cache_dir() -> Optional[str]:
    """Where JAX's persistent compilation cache lives:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else the
    fixed ``<checkout>/.cache/jax``. The path is part of JAX's cache
    key, so it is never derived from a pid, a clock or a temp name.
    None where neither exists: a package imported from an archive (the
    amalgamated predict library) has no checkout to keep a cache in."""
    named = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if named:
        return named
    if os.path.isdir(_CHECKOUT):
        return os.path.join(_CHECKOUT, ".cache", "jax")
    return None


def configure_jax_cache():
    """Called once at ``import mxnet_tpu``. With
    ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it and
    nothing is touched; otherwise JAX's cache is pointed at
    :func:`jax_cache_dir`."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") and jax_cache_dir():
        import jax
        jax.config.update("jax_compilation_cache_dir", jax_cache_dir())


def _store_root() -> Optional[str]:
    base = jax_cache_dir()
    root = getenv("MXTPU_COMPILE_CACHE_DIR",
                  base and os.path.join(base, "mxtpu-executables"))
    # expanduser like every other user-supplied root in the repo — env
    # files and CI yaml pass '~' without shell expansion
    return root and os.path.expanduser(root)

_lock = threading.Lock()
_counters: Dict[str, int] = {}


def _count(key: str, n: int = 1):
    with _lock:
        _counters[key] = _counters.get(key, 0) + n


def cache_stats() -> Dict[str, int]:
    """Hit/miss/invalidation/write/eviction/bypass counters."""
    with _lock:
        base = {"hits": 0, "misses": 0, "invalidations": 0, "writes": 0,
                "evictions": 0, "bypasses": 0}
        base.update(_counters)
        return base


def reset_cache_stats():
    with _lock:
        _counters.clear()


def cache_enabled() -> bool:
    """The ``MXTPU_COMPILE_CACHE=0`` kill switch (read per call — tests
    and operators flip it at runtime); off too where the store has no
    place to be (:func:`jax_cache_dir`)."""
    return (bool(getenv("MXTPU_COMPILE_CACHE", 1, int))
            and _store_root() is not None)


class CompilationCache:
    """One on-disk executable store. Thread-safe; multi-process-safe by
    construction (atomic renames; concurrent writers of the same key
    converge on identical content)."""

    def __init__(self, root: Optional[str] = None,
                 max_bytes: Optional[int] = None):
        self.root = (_store_root() if root is None
                     else os.path.expanduser(str(root)))
        if max_bytes is None:
            max_bytes = int(getenv("MXTPU_COMPILE_CACHE_MB", 512, float)
                            * (1 << 20))
        self.max_bytes = int(max_bytes)
        self._io_lock = threading.Lock()
        # approximate running payload total so put() only pays the full
        # directory walk when the bound is actually crossed; initialized
        # lazily from one entries() scan, then maintained incrementally
        self._approx_bytes: Optional[int] = None

    # -- paths ---------------------------------------------------------------

    def _paths(self, key: str):
        d = os.path.join(self.root, key[:2])
        return (os.path.join(d, key + ".bin"),
                os.path.join(d, key + ".manifest.json"))

    # -- read ----------------------------------------------------------------

    def get(self, key: str) -> Optional[bytes]:
        """Verified payload for ``key``, or None (miss/corrupt/fault).

        Counts a hit or miss. A VERIFIED-corrupt entry (bad digest,
        truncation, unparseable manifest) counts an invalidation and is
        quarantined. A transient read failure (I/O error, the injected
        ``compiler.cache.read`` fault) reads as a plain miss WITHOUT
        quarantining — the entry may be perfectly good once the disk
        recovers, and the worst case either way is one recompile."""
        from ..resilience import faults
        bin_path, man_path = self._paths(key)
        try:
            faults.fault_point("compiler.cache.read")
            if not (os.path.exists(bin_path) and os.path.exists(man_path)):
                _count("misses")
                return None
            with open(man_path, "r", encoding="utf-8") as f:
                raw_manifest = f.read()
            with open(bin_path, "rb") as f:
                data = f.read()
        except (OSError, TimeoutError) as err:
            logging.warning("compile cache read for %s failed (%s); "
                            "recompiling — entry left in place", key[:12],
                            err)
            _count("read_faults")
            _count("misses")
            return None
        import hashlib

        def _verify(manifest_text, payload):
            doc = json.loads(manifest_text)
            entry = doc["entry"]
            if len(payload) != entry["size"]:
                raise ValueError("payload truncated")
            if hashlib.sha256(payload).hexdigest() != entry["sha256"]:
                raise ValueError("digest mismatch (corrupt write?)")

        try:
            _verify(raw_manifest, data)
        except (ValueError, KeyError, TypeError) as first_err:
            # one re-read before condemning the entry: a concurrent
            # writer's atomic bin-then-manifest pair can interleave with
            # this read (old manifest + new payload); after the re-read
            # both files are from one completed put, so a remaining
            # mismatch is real corruption
            try:
                with open(man_path, "r", encoding="utf-8") as f:
                    raw_manifest = f.read()
                with open(bin_path, "rb") as f:
                    data = f.read()
                _verify(raw_manifest, data)
            except (OSError, ValueError, KeyError, TypeError):
                logging.warning("compile cache entry %s rejected (%s); "
                                "quarantined — recompiling", key[:12],
                                first_err)
                self._quarantine(key)
                _count("invalidations")
                _count("misses")
                return None
        _count("hits")
        # LRU touch: hits refresh recency so eviction drops cold entries
        now = time.time()
        for p in (bin_path, man_path):
            try:
                os.utime(p, (now, now))
            except OSError:
                pass
        return data

    def _quarantine(self, key: str):
        bin_path, man_path = self._paths(key)
        for p in (bin_path, man_path):
            try:
                os.remove(p)
            except OSError:
                pass

    def invalidate(self, key: str):
        """Public invalidation: quarantine ``key`` and count it. The one
        entry point for callers (the AOT loader) that discover an entry
        is unusable AFTER a digest-valid read — e.g. the payload fails
        to deserialize — so the invalidation contract has a single
        definition."""
        self._quarantine(key)
        _count("invalidations")

    # -- write ---------------------------------------------------------------

    def put(self, key: str, data: bytes, meta: Optional[dict] = None,
            counter: str = "writes"):
        """Atomically store ``data`` under ``key`` + its manifest, then
        enforce the size bound. Failures are logged, never raised — a
        full or read-only disk costs the warm start, not the run.
        ``counter`` names the statistic the write is counted under
        (``writes`` are executables)."""
        from ..resilience.checkpoint import atomic_write_bytes, file_digest
        bin_path, man_path = self._paths(key)
        try:
            os.makedirs(os.path.dirname(bin_path), exist_ok=True)
            with self._io_lock:
                atomic_write_bytes(bin_path, data)
                doc = {"format_version": MANIFEST_VERSION, "key": key,
                       "created": time.time(),
                       "entry": {"file": os.path.basename(bin_path),
                                 "size": len(data),
                                 "sha256": file_digest(bin_path)},
                       "meta": meta or {}}
                atomic_write_bytes(man_path, json.dumps(
                    doc, indent=1, sort_keys=True).encode("utf-8"))
            _count(counter)
            if self._approx_bytes is None:
                self._approx_bytes = self.total_bytes()
            else:
                self._approx_bytes += len(data)
            if self._approx_bytes > self.max_bytes:
                self.evict()
        except OSError as err:
            logging.warning("compile cache write for %s failed: %s",
                            key[:12], err)

    # -- size bound ----------------------------------------------------------

    def entries(self):
        """[(key, bytes, mtime)] for every complete entry."""
        out = []
        try:
            shards = os.listdir(self.root)
        except OSError:
            return out
        for shard in shards:
            d = os.path.join(self.root, shard)
            try:
                names = os.listdir(d)
            except OSError:
                continue
            for name in names:
                if not name.endswith(".bin"):
                    continue
                path = os.path.join(d, name)
                try:
                    st = os.stat(path)
                except OSError:
                    continue
                out.append((name[:-4], st.st_size, st.st_mtime))
        return out

    def total_bytes(self) -> int:
        return sum(size for _k, size, _m in self.entries())

    def evict(self):
        """Drop least-recently-used entries until under ``max_bytes``.
        One full scan — the put() path only calls this when the
        incremental byte estimate crosses the bound."""
        entries = sorted(self.entries(), key=lambda e: e[2])  # oldest first
        total = sum(size for _k, size, _m in entries)
        for key, size, _mtime in entries:
            if total <= self.max_bytes:
                break
            self._quarantine(key)
            total -= size
            _count("evictions")
        self._approx_bytes = total

    def clear(self):
        for key, _size, _mtime in self.entries():
            self._quarantine(key)
        self._approx_bytes = 0


_DEFAULT: Optional[CompilationCache] = None
_default_lock = threading.Lock()


def default_cache() -> CompilationCache:
    """Process-wide cache instance. Re-created when
    ``MXTPU_COMPILE_CACHE_DIR`` or ``MXTPU_COMPILE_CACHE_MB`` changes
    (tests point the dir at tmp roots and shrink the bound)."""
    global _DEFAULT
    with _default_lock:
        want = _store_root()
        want_bytes = int(getenv("MXTPU_COMPILE_CACHE_MB", 512, float)
                         * (1 << 20))
        if _DEFAULT is None or _DEFAULT.root != want \
                or _DEFAULT.max_bytes != want_bytes:
            _DEFAULT = CompilationCache(root=want, max_bytes=want_bytes)
        return _DEFAULT
