"""ctypes bindings for the native C++ runtime (cpp/mxtpu_runtime.cc).

The reference implements its IO pipeline and storage managers in C++
(src/io/iter_image_recordio_2.cc, src/storage/); this module loads the
TPU-native equivalents: a pread-based RecordIO reader/indexer, a
libjpeg batch decoder running on C++ threads (no GIL), and a
size-bucketed buffer pool with statistics.

The shared library is an untracked build product: it is built on demand
from the tracked sources with the system toolchain (``make -C cpp``)
whenever it is absent or older than them.  If the build or load fails —
no g++, no libjpeg — ``available()`` returns False, every consumer falls
back to the pure-Python path, and that is logged once, loudly: the
fallback is slower, never silent.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

import numpy as np

__all__ = ["available", "lib", "recordio_index", "decode_batch",
           "pool_stats", "pool_clear", "RecordReader"]

_logger = logging.getLogger("mxnet_tpu.native")

_CPP_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "cpp")
_SO = os.path.join(_CPP_DIR, "libmxtpu_runtime.so")

_lock = threading.Lock()
_lib = None
_tried = False


def _load():
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            sources = [os.path.join(_CPP_DIR, f)
                       for f in os.listdir(_CPP_DIR)
                       if f.endswith((".cc", ".h"))]
            if not os.path.exists(_SO) or os.path.getmtime(_SO) < max(
                    os.path.getmtime(f) for f in sources):
                subprocess.run(["make", "-C", _CPP_DIR,
                                "libmxtpu_runtime.so"], check=True,
                               capture_output=True, text=True)
            lib = ctypes.CDLL(_SO)
        except (OSError, subprocess.CalledProcessError) as e:
            _logger.error(
                "NATIVE RUNTIME UNAVAILABLE — falling back to the "
                "pure-Python IO/storage paths (slower).  Building/loading "
                "%s failed: %s%s", _SO, e,
                "\n" + e.stderr[-2000:]
                if getattr(e, "stderr", None) else "")
            _lib = None
            return None
        lib.mxtpu_recordio_open.restype = ctypes.c_void_p
        lib.mxtpu_recordio_open.argtypes = [ctypes.c_char_p]
        lib.mxtpu_recordio_close.argtypes = [ctypes.c_void_p]
        lib.mxtpu_recordio_index.restype = ctypes.c_int64
        lib.mxtpu_recordio_index.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64]
        lib.mxtpu_recordio_read_at.restype = ctypes.c_int64
        lib.mxtpu_recordio_read_at.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64]
        lib.mxtpu_decode_batch.restype = ctypes.c_int64
        lib.mxtpu_decode_batch.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_float), ctypes.c_int, ctypes.c_int,
            ctypes.c_int]
        lib.mxtpu_pool_stats.argtypes = [ctypes.POINTER(ctypes.c_int64)]
        lib.mxtpu_version.restype = ctypes.c_int
        _lib = lib
        return _lib


def available():
    return _load() is not None


def lib():
    l = _load()
    if l is None:
        raise RuntimeError("native runtime unavailable "
                           "(cpp/libmxtpu_runtime.so failed to build)")
    return l


def recordio_index(path):
    """Record byte offsets of a .rec file via the native scanner."""
    l = lib()
    cap = 1 << 16
    while True:
        buf = (ctypes.c_int64 * cap)()
        n = l.mxtpu_recordio_index(path.encode(), buf, cap)
        if n < 0:
            raise RuntimeError("native recordio: bad framing in %s" % path)
        if n <= cap:
            return list(buf[:n])
        cap = int(n)


def decode_batch(path, positions, out_h, out_w, threads=4):
    """Read + JPEG-decode records into an (N, H, W, 3) uint8 batch and
    a label vector, entirely on C++ threads.  Returns
    (batch, labels, n_failed)."""
    l = lib()
    n = len(positions)
    pos = (ctypes.c_int64 * n)(*[int(p) for p in positions])
    batch = np.empty((n, out_h, out_w, 3), np.uint8)
    labels = np.empty((n,), np.float32)
    failed = l.mxtpu_decode_batch(
        path.encode(), pos, n,
        batch.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out_h, out_w, int(threads))
    return batch, labels, int(failed)


class RecordReader:
    """pread-based record access (thread safe, shared handle)."""

    def __init__(self, path):
        self._l = lib()
        self._h = self._l.mxtpu_recordio_open(path.encode())
        if not self._h:
            raise OSError("cannot open %s" % path)
        self._cap = 1 << 20
        self._buf = (ctypes.c_uint8 * self._cap)()

    def read_at(self, pos):
        n = self._l.mxtpu_recordio_read_at(self._h, int(pos), self._buf,
                                           self._cap)
        if n < 0:
            raise RuntimeError("bad record at %d" % pos)
        if n > self._cap:
            self._cap = int(n)
            self._buf = (ctypes.c_uint8 * self._cap)()
            n = self._l.mxtpu_recordio_read_at(self._h, int(pos),
                                               self._buf, self._cap)
            if n < 0:
                raise RuntimeError("record at %d vanished mid-read" % pos)
        return bytes(self._buf[:n])

    def close(self):
        if self._h:
            self._l.mxtpu_recordio_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def pool_stats():
    """Storage-manager counters (reference pooled storage stats):
    dict with bytes_allocated/bytes_pooled/n_alloc/n_reuse/n_free."""
    l = lib()
    out = (ctypes.c_int64 * 5)()
    l.mxtpu_pool_stats(out)
    keys = ("bytes_allocated", "bytes_pooled", "n_alloc", "n_reuse",
            "n_free")
    return dict(zip(keys, out))


def pool_clear():
    lib().mxtpu_pool_clear()
