"""Bulk latency-line formatting with a native fast path.

`format_block` renders all of one message's latencies-file lines. The pure
numpy/Python implementation is fine up to a few thousand receivers; above
that the C++ emitter (native/logemit.cpp, loaded via ctypes) formats the
block in one call. The library is built with g++ the first time it is
requested, into native/liblogemit.so.<hash of logemit.cpp> (git-ignored):
the name carries the source's hash, so a binary left over from another
version of the source is never what runs. Where it cannot be built the
failure is reported once on stderr and the Python formatter takes over
(same output bytes either way).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")
_SRC = os.path.join(_NATIVE_DIR, "logemit.cpp")

# blocks of at least this many lines go to the native emitter
NATIVE_MIN_LINES = 4096

_lock = threading.Lock()
_native: ctypes.CDLL | None = None
_native_tried = False
# blocks formatted by the native library in this process (chip_smoke.py
# reports it: "was the emitter built AND used")
native_blocks = 0


def lib_path() -> str:
    """liblogemit.so.<first 16 hex digits of sha256(logemit.cpp)>."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_NATIVE_DIR, f"liblogemit.so.{digest}")


def _build(lib: str) -> None:
    # build beside the target and rename: several test workers may get
    # here at once, and none may load a half-written library
    tmp = f"{lib}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, lib)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _load_native() -> ctypes.CDLL | None:
    global _native, _native_tried
    with _lock:
        if _native_tried:
            return _native
        _native_tried = True
        try:
            path = lib_path()
            if not os.path.exists(path):
                _build(path)
            lib = ctypes.CDLL(path)
        except (OSError, subprocess.SubprocessError) as e:
            detail = getattr(e, "stderr", b"") or b""
            print(f"native log emitter unavailable, formatting in Python: "
                  f"{e!r} {detail.decode(errors='replace')[-400:]}",
                  file=sys.stderr)
            return None
        lib.format_block.restype = ctypes.c_longlong
        lib.format_block.argtypes = [
            ctypes.c_ulonglong,                  # msg_id
            ctypes.POINTER(ctypes.c_longlong),   # peers
            ctypes.POINTER(ctypes.c_longlong),   # linenos
            ctypes.POINTER(ctypes.c_longlong),   # delays
            ctypes.c_longlong,                   # count
            ctypes.c_char_p,                     # out buffer
            ctypes.c_longlong,                   # out capacity
        ]
        _native = lib
        return _native


def ensure_built() -> bool:
    """Compile (if needed) and load the native emitter; True when available.
    Used at image-build time (deploy/Dockerfile) so first boot pays no
    compile cost."""
    return _load_native() is not None


def format_block(
    msg_id: int,
    peers: np.ndarray,
    linenos: np.ndarray,
    delays: np.ndarray,
    force_python: bool = False,
) -> str:
    global native_blocks
    n = len(peers)
    lib = None if force_python else _load_native()
    if lib is not None and n >= NATIVE_MIN_LINES:
        p = np.ascontiguousarray(peers, dtype=np.int64)
        l = np.ascontiguousarray(linenos, dtype=np.int64)
        d = np.ascontiguousarray(delays, dtype=np.int64)
        # must stay >= the native side's 160-byte worst-case line guard
        cap = n * 160 + 16
        buf = ctypes.create_string_buffer(cap)
        written = lib.format_block(
            ctypes.c_ulonglong(msg_id & 0xFFFFFFFFFFFFFFFF),
            p.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
            l.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
            d.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
            n, buf, cap,
        )
        if written > 0:
            native_blocks += 1
            return buf.raw[:written].decode("ascii")
    from .logemit import grep_lines

    return "".join(line + "\n" for line in grep_lines(peers, msg_id, delays, linenos))
