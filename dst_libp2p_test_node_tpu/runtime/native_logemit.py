"""Bulk log-line formatting with a native fast path.

`format_block` renders all of one message's latencies-file lines and
`format_shadowlog` all of a `shadowlog<i>`'s. The number of lines decides
which formatter runs: under NATIVE_MIN_LINES the Python one
(logemit.grep_lines; bandwidth.shadowlog_text_python, one f-string a
peer), from there on the C++ emitter (native/logemit.cpp, loaded via
ctypes) in one call a block, counted in `native_blocks` and
`native_shadowlog_blocks`. The library is built with g++ the first time it
is requested, into native/liblogemit.so.<hash of logemit.cpp> (git-ignored):
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
# the same count for format_shadowlog
native_shadowlog_blocks = 0

# flags a shadowlog line takes from its peer: seven non-zero ones for each
# of the two remote blocks (bandwidth.shadowlog_fields; kShadowFields in
# logemit.cpp)
SHADOWLOG_FIELDS = 14


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
        lib.format_shadowlog.restype = ctypes.c_longlong
        lib.format_shadowlog.argtypes = [
            ctypes.c_char_p,                     # head of every line
            ctypes.c_longlong,                   # its length
            ctypes.POINTER(ctypes.c_longlong),   # fields, (count, 14)
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
        buf = np.empty(cap, dtype=np.uint8)     # not zeroed: all of it is slack
        written = lib.format_block(
            ctypes.c_ulonglong(msg_id & 0xFFFFFFFFFFFFFFFF),
            p.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
            l.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
            d.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
            n, buf.ctypes.data_as(ctypes.c_char_p), cap,
        )
        if written > 0:
            native_blocks += 1
            return str(buf[:written], "ascii")
    from .logemit import grep_lines

    return "".join(line + "\n" for line in grep_lines(peers, msg_id, delays, linenos))


def format_shadowlog(
    head: str, fields: np.ndarray, force_python: bool = False
) -> str:
    """All of a shadowlog's lines, peers 0..n-1: `head` (everything before
    the peer's ordinal) and `fields`, the (n, SHADOWLOG_FIELDS) integers of
    bandwidth.shadowlog_fields."""
    global native_shadowlog_blocks
    n = len(fields)
    if fields.shape != (n, SHADOWLOG_FIELDS):
        raise ValueError(f"fields must be (n, {SHADOWLOG_FIELDS}), "
                         f"not {fields.shape}")
    lib = None if force_python else _load_native()
    if lib is not None and n >= NATIVE_MIN_LINES:
        f = np.ascontiguousarray(fields, dtype=np.int64)
        h = head.encode("ascii")
        # must stay >= the native side's worst-case line guard
        cap = n * (len(h) + 512) + 16
        buf = np.empty(cap, dtype=np.uint8)
        written = lib.format_shadowlog(
            h, len(h), f.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
            n, buf.ctypes.data_as(ctypes.c_char_p), cap,
        )
        if written > 0:
            native_shadowlog_blocks += 1
            return str(buf[:written], "ascii")
    from .bandwidth import shadowlog_text_python

    return shadowlog_text_python(head, fields)
