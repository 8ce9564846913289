"""native — the C++ core, built on first import and loaded via ctypes.

The reference is native C++ throughout (SURVEY §2); our compute path is
JAX/XLA, but the runtime hot paths (checksums, rand, wire-frame scanning —
and, growing over time, the transport loop) are C++ here too. The build is
a single ``g++ -O3 -shared`` invocation cached next to the source; when no
toolchain is available every caller falls back to the pure-Python
implementation transparently.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "core.cpp")
_DP_SRC = os.path.join(_DIR, "dataplane.cpp")

_lib = None
_lib_lock = threading.Lock()
_build_error: Optional[str] = None
_dp_lib = None
_dp_lock = threading.Lock()
_dp_build_error: Optional[str] = None


def _build_flags():
    flags = ["-O3", "-shared", "-fPIC", "-std=c++17"]
    import platform

    if platform.machine() in ("x86_64", "AMD64"):
        flags.append("-msse4.2")
    return flags


def _build_so(src: str, stem: str, extra_flags=(), headers=()) -> str:
    """Compile src to a digest-named .so next to it; raises on failure.
    ``headers``: local #includes folded into the cache digest."""
    sha = hashlib.sha256()
    for path in (src, *headers):
        with open(path, "rb") as f:
            sha.update(f.read())
    digest = sha.hexdigest()[:16]
    so = os.path.join(_DIR, f"_{stem}_{digest}.so")
    if not os.path.exists(so):
        tmp = f"{so}.{os.getpid()}.tmp"
        cmd = ["g++", *_build_flags(), *extra_flags, src, "-o", tmp]
        subprocess.run(cmd, check=True, capture_output=True, timeout=180)
        os.replace(tmp, so)
    return so


def load() -> Optional[ctypes.CDLL]:
    """Build (if needed) and load the native core; None on failure."""
    global _lib, _build_error
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _build_error is not None:
            return None
        try:
            so = _build_so(_SRC, "core")
            lib = ctypes.CDLL(so)
        except (OSError, subprocess.SubprocessError) as e:
            _build_error = f"{type(e).__name__}: {e}"
            return None
        lib.tn_crc32c.restype = ctypes.c_uint32
        lib.tn_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                  ctypes.c_uint32]
        lib.tn_fast_rand.restype = ctypes.c_uint64
        lib.tn_fast_rand.argtypes = [ctypes.POINTER(ctypes.c_uint64)]
        lib.tn_fast_rand_less_than.restype = ctypes.c_uint64
        lib.tn_fast_rand_less_than.argtypes = [
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64]
        lib.tn_frame_scan.restype = ctypes.c_int
        lib.tn_frame_scan.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.tn_abi_version.restype = ctypes.c_int
        if lib.tn_abi_version() != 1:
            _build_error = "abi mismatch"
            return None
        _lib = lib
        return _lib


def build_error() -> Optional[str]:
    return _build_error


# ---------------------------------------------------------------- dataplane
class DpEventStruct(ctypes.Structure):
    """Mirror of DpEvent in dataplane.cpp."""

    _fields_ = [
        ("kind", ctypes.c_int32),
        ("tag", ctypes.c_int32),
        ("conn_id", ctypes.c_uint64),
        ("aux", ctypes.c_int64),
        ("base", ctypes.c_void_p),
        ("meta", ctypes.c_void_p),
        ("meta_len", ctypes.c_uint64),
        ("body", ctypes.c_void_p),
        ("body_len", ctypes.c_uint64),
        ("t_ns", ctypes.c_int64),
    ]


def load_dataplane() -> Optional[ctypes.CDLL]:
    """Build (if needed) and load the dataplane engine; None on failure."""
    global _dp_lib, _dp_build_error
    with _dp_lock:
        if _dp_lib is not None:
            return _dp_lib
        if _dp_build_error is not None:
            return None
        try:
            so = _build_so(_DP_SRC, "dataplane", ("-pthread",),
                           headers=(os.path.join(_DIR, "hpack_tables.h"),))
            lib = ctypes.CDLL(so)
        except (OSError, subprocess.SubprocessError) as e:
            _dp_build_error = f"{type(e).__name__}: {e}"
            return None
        ev_p = ctypes.POINTER(DpEventStruct)
        lib.dp_abi_version.restype = ctypes.c_int
        lib.dp_rt_create.restype = ctypes.c_void_p
        lib.dp_rt_create.argtypes = [ctypes.c_int, ctypes.c_uint64]
        lib.dp_rt_shutdown.argtypes = [ctypes.c_void_p]
        lib.dp_listen.restype = ctypes.c_int
        lib.dp_listen.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_int]
        lib.dp_listener_close.restype = ctypes.c_int
        lib.dp_listener_close.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.dp_listen_port.restype = ctypes.c_int
        lib.dp_listen_port.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.dp_register_echo.restype = ctypes.c_int
        lib.dp_register_echo.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                         ctypes.c_char_p, ctypes.c_char_p]
        lib.dp_unregister_listener_echoes.restype = ctypes.c_int
        lib.dp_unregister_listener_echoes.argtypes = [ctypes.c_void_p,
                                                      ctypes.c_int]
        lib.dp_connect.restype = ctypes.c_uint64
        lib.dp_connect.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_int, ctypes.c_int,
                                   ctypes.POINTER(ctypes.c_int)]
        lib.dp_connect_tpu.restype = ctypes.c_uint64
        lib.dp_connect_tpu.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       ctypes.c_int, ctypes.c_int,
                                       ctypes.c_int,
                                       ctypes.POINTER(ctypes.c_int)]
        lib.dp_connect_tpu2.restype = ctypes.c_uint64
        lib.dp_connect_tpu2.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                        ctypes.c_int, ctypes.c_int,
                                        ctypes.c_int, ctypes.c_uint32,
                                        ctypes.c_uint32,
                                        ctypes.POINTER(ctypes.c_int)]
        lib.dp_connect_grpc.restype = ctypes.c_uint64
        lib.dp_connect_grpc.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                        ctypes.c_int, ctypes.c_int,
                                        ctypes.POINTER(ctypes.c_int)]
        lib.dp_listener_set_tpu.restype = ctypes.c_int
        lib.dp_listener_set_tpu.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                            ctypes.c_int]
        lib.dp_send.restype = ctypes.c_int
        lib.dp_send.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                ctypes.c_char_p, ctypes.c_uint64]
        lib.dp_sendv.restype = ctypes.c_int
        lib.dp_sendv.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                 ctypes.POINTER(ctypes.c_char_p),
                                 ctypes.POINTER(ctypes.c_uint64),
                                 ctypes.c_int]
        lib.dp_poll.restype = ctypes.c_int
        lib.dp_poll.argtypes = [ctypes.c_void_p, ev_p, ctypes.c_int,
                                ctypes.c_int]
        lib.dp_poll_packed.restype = ctypes.c_int
        lib.dp_poll_packed.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       ctypes.c_uint64, ctypes.c_int,
                                       ctypes.c_int]
        lib.dp_free.argtypes = [ctypes.c_void_p]
        lib.dp_conn_close.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.dp_conn_stats.restype = ctypes.c_int
        lib.dp_conn_stats.argtypes = [ctypes.c_void_p, ctypes.c_uint64] + \
            [ctypes.POINTER(ctypes.c_uint64)] * 4
        lib.dp_thread_stats.restype = ctypes.c_int
        lib.dp_thread_stats.argtypes = [ctypes.c_void_p,
                                        ctypes.POINTER(ctypes.c_int64),
                                        ctypes.c_int]
        lib.dp_bench_echo.restype = ctypes.c_int
        lib.dp_bench_echo.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_uint64, ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p,
        ] + [ctypes.POINTER(ctypes.c_double)] * 5
        lib.dp_bench_echo2.restype = ctypes.c_int
        lib.dp_bench_echo2.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_uint64, ctypes.c_int, ctypes.c_char_p,
            ctypes.c_char_p,
        ] + [ctypes.POINTER(ctypes.c_double)] * 5
        # fast path (abi 2): engine-side meta parse/pack for Python RPCs
        lib.dp_listener_set_fastpath.restype = ctypes.c_int
        lib.dp_listener_set_fastpath.argtypes = [ctypes.c_void_p,
                                                 ctypes.c_int, ctypes.c_int]
        lib.dp_conn_set_fastpath.restype = ctypes.c_int
        lib.dp_conn_set_fastpath.argtypes = [ctypes.c_void_p,
                                             ctypes.c_uint64, ctypes.c_int]
        lib.dp_respond.restype = ctypes.c_int
        lib.dp_respond.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.c_uint64, ctypes.c_int, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
            ctypes.c_uint64, ctypes.c_int, ctypes.c_int]
        lib.dp_call.restype = ctypes.c_int
        lib.dp_call.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_char_p,
            ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
            ctypes.c_uint64, ctypes.c_int]
        lib.dp_flush_all.restype = ctypes.c_int
        lib.dp_flush_all.argtypes = [ctypes.c_void_p]
        lib.dp_tpu_ack.restype = ctypes.c_int
        lib.dp_tpu_ack.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                   ctypes.c_char_p, ctypes.c_uint64]
        lib.dp_svc_set_limit.restype = ctypes.c_int
        lib.dp_svc_set_limit.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                         ctypes.c_char_p, ctypes.c_char_p,
                                         ctypes.c_int]
        lib.dp_listener_set_logoff.restype = ctypes.c_int
        lib.dp_listener_set_logoff.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                               ctypes.c_int]
        lib.dp_svc_stats.restype = ctypes.c_int
        lib.dp_svc_stats.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_int32)]
        # abi 3: engine-parked sync calls (dp_call_sync) — the caller
        # blocks in C with the GIL released; the parse thread completes it
        lib.dp_call_sync.restype = ctypes.c_int
        lib.dp_call_sync.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_char_p,
            ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_uint64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int32, ctypes.c_char_p,
            ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64)]
        lib.dp_call2.restype = ctypes.c_int
        lib.dp_call2.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
            ctypes.c_uint64]
        lib.dp_respond2.restype = ctypes.c_int
        lib.dp_respond2.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64]
        lib.dp_call_sync2.restype = ctypes.c_int
        lib.dp_call_sync2.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
            ctypes.c_uint64]
        lib.dp_sync_complete_py.restype = ctypes.c_int
        lib.dp_sync_complete_py.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int32,
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
            ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64]
        if lib.dp_abi_version() != 3:
            _dp_build_error = "dataplane abi mismatch"
            return None
        _dp_lib = lib
        return _dp_lib


def dataplane_build_error() -> Optional[str]:
    return _dp_build_error


# ------------------------------------------------------------- installation
def install() -> bool:
    """Point the Python fallbacks at the native implementations.
    Returns True when the native core is active."""
    lib = load()
    if lib is None:
        return False
    from brpc_tpu.butil import misc

    def native_crc32c(data, value: int = 0) -> int:
        b = bytes(data)
        return lib.tn_crc32c(b, len(b), value)

    misc._native_crc32c = native_crc32c

    # entropy-seeded, like the Python fallback (identical sequences across
    # a fleet would synchronize "random" LB picks and jitter)
    state = ctypes.c_uint64(
        int.from_bytes(os.urandom(8), "little") | 1)

    def native_fast_rand() -> int:
        return lib.tn_fast_rand(ctypes.byref(state))

    def native_fast_rand_less_than(n: int) -> int:
        return lib.tn_fast_rand_less_than(ctypes.byref(state), n) if n > 0 else 0

    misc._native_fast_rand = native_fast_rand
    misc._native_fast_rand_less_than = native_fast_rand_less_than
    return True


class FrameScanner:
    """Batched TRPC/TSTR frame-boundary scanner over a contiguous buffer."""

    def __init__(self, max_frames: int = 128):
        self._lib = load()
        self.max_frames = max_frames
        self._offsets = (ctypes.c_uint64 * (3 * max_frames))()
        self._consumed = ctypes.c_uint64()

    @property
    def available(self) -> bool:
        return self._lib is not None

    def scan(self, data: bytes, max_body: int):
        """Returns (frames, consumed, bad) where frames is a list of
        (start, meta_size, body_size) for each COMPLETE frame."""
        n = self._lib.tn_frame_scan(
            data, len(data), max_body, self._offsets, self.max_frames,
            ctypes.byref(self._consumed))
        bad = n < 0
        frames = [(self._offsets[i * 3], self._offsets[i * 3 + 1],
                   self._offsets[i * 3 + 2]) for i in range(max(n, 0))]
        return frames, self._consumed.value, bad
