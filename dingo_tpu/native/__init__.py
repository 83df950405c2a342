"""ctypes bindings to the native C++ runtime piece (sources in native/).

The shared library is built on demand, on the machine that runs it: the
environment guarantees g++ but no pip installs, so the repo ships sources
and compiles lazily (cached .so next to this file, git-ignored). Only the
LSM raw engine needs it; no index and not the WAL engine load native code.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess

_HERE = os.path.dirname(os.path.abspath(__file__))
_NATIVE_SRC = os.path.join(os.path.dirname(os.path.dirname(_HERE)), "native")
_CXXFLAGS = ("-O3", "-std=c++17", "-fPIC", "-shared", "-march=native")


def _cpu_identity() -> str:
    """What -march=native resolved against: the first processor's model and
    instruction-set flags. A library built under another identity may hold
    instructions this CPU lacks (AVX-512 on a host without it is SIGILL)."""
    ident = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if not line.strip():
                    break                       # first processor only
                if line.startswith(("model name", "flags", "Features")):
                    ident.append(line.strip())
    except OSError:
        pass
    return "\n".join(ident)


def _build(lib: str, src: str) -> str:
    """Compile (or reuse) a native helper library.

    A cached artifact is reused only when its stamp matches a hash of the
    source, the compiler flags AND this CPU's identity — never mtimes (git
    checkouts don't preserve them). The .so files are git-ignored but a
    tree copied from another machine carries them along: under
    -march=native such a copy is rebuilt here, never executed.
    """
    path = os.path.join(_HERE, lib)
    srcpath = os.path.join(_NATIVE_SRC, src)
    stamp = path + ".srchash"
    with open(srcpath, "rb") as f:
        h = hashlib.sha256(f.read())
    h.update(" ".join(_CXXFLAGS).encode())
    h.update(_cpu_identity().encode())
    want = h.hexdigest()
    have = None
    if os.path.exists(stamp):
        with open(stamp) as f:
            have = f.read().strip()
    if not os.path.exists(path) or have != want:
        # build to a private temp then os.replace: concurrent importers
        # (pytest -n, two servers on one checkout) must never dlopen a
        # half-written .so
        tmp = f"{path}.build.{os.getpid()}"
        try:
            subprocess.run(
                ["g++", *_CXXFLAGS, srcpath, "-o", tmp],
                check=True,
                capture_output=True,
            )
        except FileNotFoundError as e:
            raise RuntimeError(
                f"{lib} is built from native/{src} on first use and g++ is "
                "missing; the LSM engine needs it"
            ) from e
        tmp_stamp = f"{stamp}.{os.getpid()}"
        with open(tmp_stamp, "w") as f:
            f.write(want)
        os.replace(tmp, path)
        os.replace(tmp_stamp, stamp)
    return path


def load_lsm() -> ctypes.CDLL:
    lib = ctypes.CDLL(_build("libdingolsm.so", "lsm/lsm.cc"))
    c = ctypes
    lib.lsm_open.restype = c.c_void_p
    lib.lsm_open.argtypes = [c.c_char_p, c.c_uint64, c.c_int]
    lib.lsm_close.argtypes = [c.c_void_p]
    lib.lsm_write.restype = c.c_int
    lib.lsm_write.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64]
    lib.lsm_get.restype = c.c_int
    lib.lsm_get.argtypes = [
        c.c_void_p, c.c_char_p, c.c_uint64,
        c.POINTER(c.POINTER(c.c_char)), c.POINTER(c.c_uint64),
    ]
    lib.lsm_free_buf.argtypes = [c.POINTER(c.c_char)]
    lib.lsm_scan.restype = c.c_void_p
    lib.lsm_scan.argtypes = [
        c.c_void_p, c.c_char_p, c.c_uint64, c.c_char_p, c.c_uint64,
        c.c_int, c.c_int,
    ]
    lib.lsm_iter_next.restype = c.c_int
    lib.lsm_iter_next.argtypes = [
        c.c_void_p, c.POINTER(c.POINTER(c.c_char)), c.POINTER(c.c_uint64),
        c.POINTER(c.POINTER(c.c_char)), c.POINTER(c.c_uint64),
    ]
    lib.lsm_iter_close.argtypes = [c.c_void_p]
    lib.lsm_count.restype = c.c_uint64
    lib.lsm_count.argtypes = [
        c.c_void_p, c.c_char_p, c.c_uint64, c.c_char_p, c.c_uint64, c.c_int,
    ]
    lib.lsm_flush.restype = c.c_int
    lib.lsm_flush.argtypes = [c.c_void_p]
    lib.lsm_compact.restype = c.c_int
    lib.lsm_compact.argtypes = [c.c_void_p]
    lib.lsm_sst_count.restype = c.c_uint64
    lib.lsm_sst_count.argtypes = [c.c_void_p]
    lib.lsm_delete_range.restype = c.c_int64
    lib.lsm_delete_range.argtypes = [
        c.c_void_p, c.c_char_p, c.c_uint64, c.c_char_p, c.c_uint64, c.c_int,
    ]
    lib.lsm_index_bytes.restype = c.c_uint64
    lib.lsm_index_bytes.argtypes = [c.c_void_p]
    return lib
