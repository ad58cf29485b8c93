"""ctypes bindings for the native IO library.

Reference analogue: python/mxnet/base.py ``_load_lib`` loading libmxnet.so.
Here the native surface is only the runtime around the compute path (the
compute path is XLA); ``libmxtpu_io.so`` provides GIL-free bulk RecordIO.

The library is built by ``make`` (repo root) into the git-ignored
``mxnet_tpu/_lib/``. If it is missing, or older than ``src/io/recordio.cc``,
we compile it on demand with g++; failing that, callers fall back to the
pure-python path — the framework stays fully functional without a
toolchain — and a WARNING says which reader is in use.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

import numpy as np

_LIB = None
_LIB_LOCK = threading.Lock()


class NativeUnavailableError(OSError):
    """The native library could not be loaded/built (callers may fall back
    to pure python). File-level errors raise plain OSError/IOError and must
    NOT be swallowed by fallbacks."""
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LIB_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "_lib", "libmxtpu_io.so")
_SRC = os.path.join(_REPO_ROOT, "src", "io", "recordio.cc")


def _try_build():
    if not os.path.exists(_SRC):
        return False
    os.makedirs(os.path.dirname(_LIB_PATH), exist_ok=True)
    # several processes build at once in a fresh checkout (every xdist
    # worker imports the tests that need the lib): each links its own
    # file and renames it, so the path holds a whole library or none
    tmp = "%s.%d.tmp" % (_LIB_PATH, os.getpid())
    cmd = ["g++", "-O2", "-std=c++17", "-fPIC", "-Wall", _SRC,
           "-shared", "-pthread", "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _LIB_PATH)
        return True
    except (OSError, subprocess.SubprocessError):
        return False
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _bind(lib):
    lib.MXTRecordReaderOpen.restype = ctypes.c_void_p
    lib.MXTRecordReaderOpen.argtypes = [ctypes.c_char_p]
    lib.MXTRecordReaderClose.argtypes = [ctypes.c_void_p]
    lib.MXTRecordReaderNumRecords.restype = ctypes.c_int64
    lib.MXTRecordReaderNumRecords.argtypes = [ctypes.c_void_p]
    lib.MXTRecordReaderRecordLen.restype = ctypes.c_int64
    lib.MXTRecordReaderRecordLen.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.MXTRecordReaderRecordOffset.restype = ctypes.c_int64
    lib.MXTRecordReaderRecordOffset.argtypes = [ctypes.c_void_p,
                                                ctypes.c_int64]
    lib.MXTRecordReaderRead.restype = ctypes.c_int64
    lib.MXTRecordReaderRead.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                        ctypes.c_void_p]
    lib.MXTRecordReaderBatchLen.restype = ctypes.c_int64
    lib.MXTRecordReaderBatchLen.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                            ctypes.c_int64]
    lib.MXTRecordReaderReadBatch.restype = ctypes.c_int64
    lib.MXTRecordReaderReadBatch.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int]
    lib.MXTRecordReaderSaveIndex.restype = ctypes.c_int64
    lib.MXTRecordReaderSaveIndex.argtypes = [ctypes.c_void_p,
                                             ctypes.c_char_p]
    lib.MXTGetLastError.restype = ctypes.c_char_p
    return lib


def get_lib():
    """Load (building if necessary) the native lib; None if unavailable.

    Disable with MXNET_TPU_NO_NATIVE=1 (the NaiveEngine-style escape
    hatch for debugging)."""
    global _LIB
    if os.environ.get("MXNET_TPU_NO_NATIVE", "0") == "1":
        return None
    with _LIB_LOCK:
        if _LIB is not None:
            return _LIB or None
        have = os.path.exists(_LIB_PATH)
        stale = (have and os.path.exists(_SRC)
                 and os.path.getmtime(_SRC) > os.path.getmtime(_LIB_PATH))
        if (not have or stale) and not _try_build():
            if not have:
                logging.warning("native IO library unavailable (no %s and "
                                "g++ could not build it from %s): the "
                                "pure-python reader serves", _LIB_PATH, _SRC)
                _LIB = False
                return None
            logging.warning("%s is older than %s and could not be rebuilt: "
                            "loading the stale library", _LIB_PATH, _SRC)
        try:
            _LIB = _bind(ctypes.CDLL(_LIB_PATH))
        except OSError as err:
            logging.warning("native IO library %s failed to load (%s): the "
                            "pure-python reader serves", _LIB_PATH, err)
            _LIB = False
            return None
        return _LIB or None


class NativeRecordReader:
    """Random-access .rec reader over the native lib.

    Thread-safe (pread inside); ``read_batch`` fans reads over a C++
    thread pool with the GIL released for the duration of the call.
    """

    def __init__(self, path, nthreads=4):
        lib = get_lib()
        if lib is None:
            raise NativeUnavailableError("native IO library unavailable")
        self._lib = lib
        self._path = path
        self._h = lib.MXTRecordReaderOpen(path.encode())
        if not self._h:
            raise OSError("MXTRecordReaderOpen failed: "
                          + lib.MXTGetLastError().decode())
        self._n = lib.MXTRecordReaderNumRecords(self._h)
        self._nthreads = nthreads

    def __len__(self):
        return self._n

    def __getstate__(self):
        return {"path": self._path, "nthreads": self._nthreads}

    def __setstate__(self, d):
        self.__init__(d["path"], d["nthreads"])

    def offset(self, i: int) -> int:
        """File offset of record i's header (= the .idx sidecar value)."""
        off = self._lib.MXTRecordReaderRecordOffset(self._h, i)
        if off < 0:
            raise IndexError(f"record {i} out of range (n={self._n})")
        return off

    def offsets(self):
        """Offset -> scan position map for all records."""
        return {self.offset(i): i for i in range(self._n)}

    def close(self):
        if getattr(self, "_h", None):
            self._lib.MXTRecordReaderClose(self._h)
            self._h = None

    def __del__(self):
        self.close()

    def read(self, i: int) -> bytes:
        length = self._lib.MXTRecordReaderRecordLen(self._h, i)
        if length < 0:
            raise IndexError(f"record {i} out of range (n={self._n})")
        buf = ctypes.create_string_buffer(length)
        got = self._lib.MXTRecordReaderRead(self._h, i, buf)
        if got != length:
            raise IOError(self._lib.MXTGetLastError().decode())
        return buf.raw

    def read_batch(self, indices):
        """Read many records at once -> list of bytes (parallel pread)."""
        idx = np.ascontiguousarray(indices, dtype=np.int64)
        n = len(idx)
        if n == 0:
            return []
        lens = np.empty(n, dtype=np.int64)
        offsets = np.empty(n, dtype=np.int64)
        total = self._lib.MXTRecordReaderBatchLen(self._h, idx.ctypes.data, n)
        if total < 0:
            raise IndexError(self._lib.MXTGetLastError().decode())
        out = np.empty(total, dtype=np.uint8)
        got = self._lib.MXTRecordReaderReadBatch(
            self._h, idx.ctypes.data, n, out.ctypes.data, total,
            offsets.ctypes.data, lens.ctypes.data, self._nthreads)
        if got < 0:
            raise IOError(self._lib.MXTGetLastError().decode())
        return [out[offsets[k]:offsets[k] + lens[k]].tobytes()
                for k in range(n)]

    def save_index(self, idx_path: str) -> int:
        n = self._lib.MXTRecordReaderSaveIndex(self._h, idx_path.encode())
        if n < 0:
            raise IOError(self._lib.MXTGetLastError().decode())
        return n
