"""Native library loader — builds and binds ``libmxtpu_io.so``.

The reference ships its data path as C++ (dmlc recordio + the OMP decode
pipeline of src/io/iter_image_recordio_2.cc); this package compiles the
TPU rebuild's native equivalents from ``native/*.cc`` on first use and
exposes them over ctypes (the framework's C-ABI boundary, standing in for
the reference's ``libmxnet.so`` C API surface).

Build is a single g++ invocation — no cmake dance for two translation
units.  The output is stale when the hash of the sources differs from the
one recorded beside it at build time; mtimes say nothing after a copy or
a checkout.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_LOCK = threading.Lock()
_LIB = None

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC_DIR = os.path.join(_ROOT, "native")
_SOURCES = ("recordio.cc", "image_pipeline.cc")
_OUT = os.path.join(_SRC_DIR, "build", "libmxtpu_io.so")
_STAMP = _OUT + ".sha256"


class NativeBuildError(RuntimeError):
    pass


def _source_hash() -> str:
    h = hashlib.sha256()
    for name in _SOURCES + ("recordio.h",):
        with open(os.path.join(_SRC_DIR, name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read() + b"\0")
    return h.hexdigest()


def _needs_build() -> bool:
    try:
        with open(_STAMP) as f:
            built_from = f.read().strip()
    except OSError:
        return True
    return not os.path.exists(_OUT) or built_from != _source_hash()


def _build() -> None:
    os.makedirs(os.path.dirname(_OUT), exist_ok=True)
    src_hash = _source_hash()
    tmp = "%s.%d.tmp" % (_OUT, os.getpid())
    cmd = [
        "g++", "-O3", "-fPIC", "-shared", "-std=c++17", "-pthread",
        "-Wall", "-Wextra", "-Wno-unused-parameter",
    ] + [os.path.join(_SRC_DIR, s) for s in _SOURCES] + [
        "-o", tmp, "-ljpeg",
    ]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise NativeBuildError(
            "native build failed:\n%s\n%s" % (" ".join(cmd), proc.stderr)
        )
    # rename into place: a concurrent process never loads a half-written
    # library, and the stamp is written only after the library it names
    os.replace(tmp, _OUT)
    with open(_STAMP + ".tmp", "w") as f:
        f.write(src_hash + "\n")
    os.replace(_STAMP + ".tmp", _STAMP)


def lib() -> ctypes.CDLL:
    """Load (building if stale) the native IO library."""
    global _LIB
    with _LOCK:
        if _LIB is not None:
            return _LIB
        if _needs_build():
            _build()
        L = ctypes.CDLL(_OUT)

        # recordio
        L.MXTPURecordIOWriterCreate.argtypes = [ctypes.c_char_p,
                                                ctypes.POINTER(ctypes.c_void_p)]
        L.MXTPURecordIOWriterWrite.argtypes = [ctypes.c_void_p,
                                               ctypes.c_char_p, ctypes.c_size_t]
        L.MXTPURecordIOWriterTell.argtypes = [ctypes.c_void_p,
                                              ctypes.POINTER(ctypes.c_size_t)]
        L.MXTPURecordIOWriterFree.argtypes = [ctypes.c_void_p]
        L.MXTPURecordIOReaderCreate.argtypes = [ctypes.c_char_p,
                                                ctypes.POINTER(ctypes.c_void_p)]
        L.MXTPURecordIOReaderRead.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_char)),
            ctypes.POINTER(ctypes.c_size_t)]
        L.MXTPURecordIOReaderSeek.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
        L.MXTPURecordIOReaderTell.argtypes = [ctypes.c_void_p,
                                              ctypes.POINTER(ctypes.c_size_t)]
        L.MXTPURecordIOReaderFree.argtypes = [ctypes.c_void_p]
        L.MXTPURecordIOGetLastError.restype = ctypes.c_char_p

        # image iter
        L.MXTPUImageIterCreate.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_void_p)]
        L.MXTPUImageIterNext.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
            ctypes.POINTER(ctypes.c_int)]
        L.MXTPUImageIterReset.argtypes = [ctypes.c_void_p]
        L.MXTPUImageIterFree.argtypes = [ctypes.c_void_p]
        L.MXTPUImageIterNumRecords.argtypes = [ctypes.c_void_p,
                                               ctypes.POINTER(ctypes.c_size_t)]
        L.MXTPUImageIterGetLastError.restype = ctypes.c_char_p

        _LIB = L
        return _LIB


def last_error() -> str:
    return lib().MXTPURecordIOGetLastError().decode()
