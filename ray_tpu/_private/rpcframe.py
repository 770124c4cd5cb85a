"""ctypes binding to the native RPC wire framer (src/rpcframe).

Same build discipline as the shm store (`_shmstore.so`): built on first
use with g++ (no pip deps), committed alongside the source so a
compiler-less host can still load it.  Unlike the store, the framer is
OPTIONAL: every entry point degrades gracefully — `available()` returns
False (after one warning) on a missing compiler, a corrupt `.so`, or an
ABI mismatch, and rpc.py then runs its byte-compatible pure-Python
framing.  A cluster may freely mix native and pure-Python nodes: the
wire format is identical (see docs/data_plane.md "Native framer").

Exposes three primitives consumed by rpc.Connection:

  Scanner      streaming msgpack boundary scanner: splits a stream chunk
               into CONTROL spans, RAW_BEGIN headers and RAW payload
               spans without building Python objects or resetting the
               decoder on raw headers.
  writev()     gather-write a list of buffers in one (looping) writev —
               a whole frame wave or raw header + arena views per
               syscall; stops at EAGAIN and reports how far it got.
  recv_into()  drain a socket directly into a destination buffer (the
               shm arena region of an in-flight pull) until the payload
               completes or the socket would block.
"""

from __future__ import annotations

import ctypes
import logging
import os
import threading
from typing import List, Tuple

from . import native_build

logger = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "src", "rpcframe", "rpcframe.cc")
_SO = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "_rpcframe.so")

ABI_VERSION = 1

# Event types (keep in sync with rpcframe.cc).
EV_CTRL = 0
EV_RAW_BEGIN = 1
EV_RAW_DATA = 2
EV_STASH_CTRL = 3

_build_lock = threading.Lock()
_lib = None
_failed = False


def ensure_built() -> str:
    """Build (or reuse) the shared object for the source as it reads now;
    raises when it cannot be built (_load() then degrades to the
    pure-Python framer, loudly)."""
    with _build_lock:
        return native_build.build_so(_SRC, _SO)


def _load():
    global _lib, _failed
    if _lib is not None:
        return _lib
    if _failed:
        return None
    try:
        lib = ctypes.CDLL(ensure_built())
        lib.rf_abi_version.restype = ctypes.c_int
        if lib.rf_abi_version() != ABI_VERSION:
            raise OSError(
                f"_rpcframe.so ABI {lib.rf_abi_version()} != {ABI_VERSION}")
        lib.rf_scanner_new.restype = ctypes.c_void_p
        lib.rf_scanner_free.argtypes = [ctypes.c_void_p]
        lib.rf_scanner_reset.argtypes = [ctypes.c_void_p]
        lib.rf_scanner_set_raw_remaining.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64]
        lib.rf_scanner_raw_remaining.argtypes = [ctypes.c_void_p]
        lib.rf_scanner_raw_remaining.restype = ctypes.c_uint64
        lib.rf_scanner_spill_ptr.argtypes = [ctypes.c_void_p]
        lib.rf_scanner_spill_ptr.restype = ctypes.c_void_p
        lib.rf_scan.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.rf_scan.restype = ctypes.c_int64
        lib.rf_writev.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_int32,
            ctypes.c_uint64, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32)]
        lib.rf_writev.restype = ctypes.c_int64
        lib.rf_recv_into.argtypes = [
            ctypes.c_int, ctypes.c_void_p, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32)]
        lib.rf_recv_into.restype = ctypes.c_int64
        _lib = lib
        return lib
    except Exception as e:  # noqa: BLE001 — any failure means fallback
        _failed = True
        logger.warning(
            "native RPC framer unavailable (%s: %s); falling back to the "
            "pure-Python framing (wire-compatible, slower bulk paths)",
            type(e).__name__, e)
        return None


def available() -> bool:
    return _load() is not None


def _reset_for_tests(so_path: str | None = None) -> None:
    """Drop the cached library/failure state (and optionally repoint the
    .so path) so fallback behavior is testable in-process."""
    global _lib, _failed, _SO
    _lib = None
    _failed = False
    if so_path is not None:
        _SO = so_path


# ---------------------------------------------------------------------------
# Buffer address extraction
# ---------------------------------------------------------------------------
def _addr_len(b) -> Tuple[int, int, object]:
    """(address, nbytes, keepalive) of a bytes-like object's payload.

    `bytes` — the dominant case: every frame in a wbuf wave — resolves
    allocation-free via a c_char_p cast (no ndarray per frame on the
    hot path this module exists to strip).  Arena memoryviews and other
    buffer exporters go through numpy; non-contiguous exotica are
    materialized (rare, small)."""
    if type(b) is bytes:
        addr = ctypes.cast(ctypes.c_char_p(b), ctypes.c_void_p).value
        return addr, len(b), b
    import numpy as np
    try:
        a = np.frombuffer(b, np.uint8)
    except (ValueError, TypeError):
        a = np.frombuffer(bytes(b), np.uint8)
    return a.ctypes.data, a.nbytes, a


def writev(fd: int, buffers: List, skip: int = 0):
    """Gather-write `buffers` (resuming `skip` bytes in) in as few
    writev syscalls as the socket accepts.  Returns
    (written, total, errno, nsyscalls): errno == 0 means success or a
    clean EAGAIN stop (written < total-skip); nonzero means a hard
    transport error."""
    lib = _load()
    n = len(buffers)
    ptrs = (ctypes.c_void_p * n)()
    lens = (ctypes.c_uint64 * n)()
    keep = []
    total = 0
    for i, b in enumerate(buffers):
        addr, nb, ka = _addr_len(b)
        keep.append(ka)
        ptrs[i] = addr
        lens[i] = nb
        total += nb
    err = ctypes.c_int32()
    nsys = ctypes.c_int32()
    w = lib.rf_writev(fd, ptrs, lens, n, skip, ctypes.byref(err),
                      ctypes.byref(nsys))
    del keep
    return w, total, err.value, nsys.value


RECV_WOULD_BLOCK = 0
RECV_EOF = 1
RECV_ERROR = 2
RECV_FILLED = 3


def recv_into(fd: int, addr: int, cap: int):
    """Drain socket `fd` into raw memory at `addr` (≤ cap bytes).
    Returns (nread, state, errno, nsyscalls)."""
    lib = _load()
    state = ctypes.c_int32()
    err = ctypes.c_int32()
    nsys = ctypes.c_int32()
    got = lib.rf_recv_into(fd, addr, cap, ctypes.byref(state),
                           ctypes.byref(err), ctypes.byref(nsys))
    return got, state.value, err.value, nsys.value


class Scanner:
    """Per-connection streaming framer state (see module docstring).

    scan(data) -> (nevents, consumed); events are read from the .evt /
    .eva / .evb arrays.  nevents == -1 means a malformed stream (the
    caller aborts the connection, mirroring the Python framer)."""

    MAX_EVENTS = 128

    __slots__ = ("_lib", "_h", "evt", "eva", "evb", "_consumed",
                 "_spill_ptr")

    def __init__(self):
        lib = _load()
        if lib is None:
            raise RuntimeError("native framer unavailable")
        self._lib = lib
        self._h = lib.rf_scanner_new()
        if not self._h:
            raise MemoryError("rf_scanner_new failed")
        self.evt = (ctypes.c_int32 * self.MAX_EVENTS)()
        self.eva = (ctypes.c_int64 * self.MAX_EVENTS)()
        self.evb = (ctypes.c_int64 * self.MAX_EVENTS)()
        self._consumed = ctypes.c_uint64()
        self._spill_ptr = lib.rf_scanner_spill_ptr(self._h)

    def scan(self, data: bytes, offset: int = 0):
        """Scan `data[offset:]` (`data` must be bytes).  Returns
        (nevents, consumed).  Offset is applied by pointer arithmetic —
        no tail copy when a chunk needs several scan calls (dense
        raw-header streams exceed the event arrays)."""
        addr = ctypes.cast(ctypes.c_char_p(data), ctypes.c_void_p).value
        n = self._lib.rf_scan(
            self._h, addr + offset, len(data) - offset, self.evt,
            self.eva, self.evb, self.MAX_EVENTS,
            ctypes.byref(self._consumed))
        return n, self._consumed.value

    def spill_bytes(self, off: int, length: int) -> bytes:
        """Stash bytes reclassified as control stream by the last scan
        (EV_STASH_CTRL events reference this buffer)."""
        return ctypes.string_at(self._spill_ptr + off, length)

    def set_raw_remaining(self, remaining: int) -> None:
        self._lib.rf_scanner_set_raw_remaining(self._h, remaining)

    def raw_remaining(self) -> int:
        return self._lib.rf_scanner_raw_remaining(self._h)

    def close(self) -> None:
        h, self._h = self._h, None
        if h:
            self._lib.rf_scanner_free(h)

    def __del__(self):  # pragma: no cover — belt and braces
        try:
            self.close()
        except Exception:
            pass
