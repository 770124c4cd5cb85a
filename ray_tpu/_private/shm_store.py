"""ctypes binding to the native shared-memory object store (src/object_store).

Equivalent of the reference's plasma client (reference:
src/ray/object_manager/plasma/client.h) — but daemonless: every process maps
the same /dev/shm arena and calls into the native library under a
process-shared robust mutex, giving zero-copy create/seal/get without a socket
round trip. The library is built on first use with g++ (no pip deps).
"""

from __future__ import annotations

import ctypes
import errno
import mmap
import os
import resource
import threading
from typing import Optional

from . import native_build

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "src", "object_store", "store.cc")
_SO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_shmstore.so")

_build_lock = threading.Lock()
_lib = None

# What an arena file holds besides its data bytes: the header and the
# default 65536-slot table (about 4 MiB), rounded up.
ARENA_OVERHEAD_BYTES = 8 << 20


def arena_bytes_limit() -> Optional[int]:
    """The most data bytes an arena can have under this process's file-size
    limit (RLIMIT_FSIZE, which children inherit), or None when there is no
    limit.  The arena is ONE file in /dev/shm, so a harness's `ulimit -f`
    bounds it like any other file."""
    soft = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
    if soft == resource.RLIM_INFINITY:
        return None
    return max(0, soft - ARENA_OVERHEAD_BYTES)


def _ensure_built() -> str:
    # Load-bearing (the store IS the data plane): a failed build raises.
    with _build_lock:
        return native_build.build_so(_SRC, _SO, ldflags=("-lpthread",))


def _load():
    global _lib
    if _lib is not None:
        return _lib
    lib = ctypes.CDLL(_ensure_built())
    lib.rts_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64]
    lib.rts_create.restype = ctypes.c_int
    lib.rts_attach.argtypes = [ctypes.c_char_p]
    lib.rts_attach.restype = ctypes.c_int
    lib.rts_detach.argtypes = [ctypes.c_int]
    lib.rts_data_offset.argtypes = [ctypes.c_int]
    lib.rts_data_offset.restype = ctypes.c_uint64
    lib.rts_capacity.argtypes = [ctypes.c_int]
    lib.rts_capacity.restype = ctypes.c_uint64
    lib.rts_total_size.argtypes = [ctypes.c_int]
    lib.rts_total_size.restype = ctypes.c_uint64
    lib.rts_create_object.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_uint64]
    lib.rts_create_object.restype = ctypes.c_int64
    lib.rts_seal.argtypes = [ctypes.c_int, ctypes.c_char_p]
    lib.rts_seal.restype = ctypes.c_int
    lib.rts_get.argtypes = [ctypes.c_int, ctypes.c_char_p,
                            ctypes.POINTER(ctypes.c_uint64), ctypes.c_int]
    lib.rts_get.restype = ctypes.c_int64
    lib.rts_release.argtypes = [ctypes.c_int, ctypes.c_char_p]
    lib.rts_release.restype = ctypes.c_int
    lib.rts_delete.argtypes = [ctypes.c_int, ctypes.c_char_p]
    lib.rts_delete.restype = ctypes.c_int
    lib.rts_contains.argtypes = [ctypes.c_int, ctypes.c_char_p]
    lib.rts_contains.restype = ctypes.c_int
    lib.rts_abort.argtypes = [ctypes.c_int, ctypes.c_char_p]
    lib.rts_abort.restype = ctypes.c_int
    lib.rts_refcount.argtypes = [ctypes.c_int, ctypes.c_char_p]
    lib.rts_refcount.restype = ctypes.c_int
    lib.rts_release_n_and_delete_if.argtypes = [
        ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
    lib.rts_release_n_and_delete_if.restype = ctypes.c_int
    lib.rts_stats.argtypes = [ctypes.c_int] + [ctypes.POINTER(ctypes.c_uint64)] * 5
    lib.rts_list_evictable.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
    lib.rts_list_evictable.restype = ctypes.c_int
    lib.rts_list_objects.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
    lib.rts_list_objects.restype = ctypes.c_int
    lib.rts_list_unsealed.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
    lib.rts_list_unsealed.restype = ctypes.c_int
    lib.rts_put_iov.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                ctypes.POINTER(ctypes.c_void_p),
                                ctypes.POINTER(ctypes.c_uint64),
                                ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.rts_put_iov.restype = ctypes.c_int
    lib.rts_chan_init.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                  ctypes.c_uint32, ctypes.c_uint64,
                                  ctypes.c_uint32]
    lib.rts_chan_init.restype = ctypes.c_int64
    lib.rts_chan_write.argtypes = [ctypes.c_int, ctypes.c_uint64,
                                   ctypes.c_char_p, ctypes.c_uint64,
                                   ctypes.c_int]
    lib.rts_chan_write.restype = ctypes.c_int
    lib.rts_chan_peek.argtypes = [ctypes.c_int, ctypes.c_uint64,
                                  ctypes.c_uint32,
                                  ctypes.POINTER(ctypes.c_uint64),
                                  ctypes.POINTER(ctypes.c_uint64),
                                  ctypes.c_int]
    lib.rts_chan_peek.restype = ctypes.c_int
    lib.rts_chan_advance.argtypes = [ctypes.c_int, ctypes.c_uint64,
                                     ctypes.c_uint32]
    lib.rts_chan_advance.restype = ctypes.c_int
    lib.rts_chan_close.argtypes = [ctypes.c_int, ctypes.c_uint64]
    lib.rts_chan_close.restype = ctypes.c_int
    lib.rts_chan_destroy.argtypes = [ctypes.c_int, ctypes.c_char_p]
    lib.rts_chan_destroy.restype = ctypes.c_int
    _lib = lib
    return lib


class ShmObjectStoreError(Exception):
    pass


class SpillTruncatedError(OSError):
    """A spill file is shorter than the object it records — the on-disk
    copy itself is damaged (vs a transient I/O error, which must NOT be
    treated as corruption)."""


class ObjectExistsError(ShmObjectStoreError):
    pass


class StoreFullError(ShmObjectStoreError):
    pass


class ShmStore:
    """A client attachment to one node's shared-memory arena."""

    def __init__(self, path: str, handle: int):
        self._lib = _load()
        self.path = path
        self._h = handle
        total = self._lib.rts_total_size(handle)
        fd = os.open(path, os.O_RDWR)
        try:
            self._mm = mmap.mmap(fd, total)
        finally:
            os.close(fd)
        self._view = memoryview(self._mm)

    # -- lifecycle -----------------------------------------------------------
    @classmethod
    def create(cls, path: str, capacity: int, table_slots: int = 1 << 16) -> "ShmStore":
        limit = arena_bytes_limit()
        fits = "" if limit is None else (
            f"; this process's file-size limit (RLIMIT_FSIZE) leaves room "
            f"for {limit} data bytes")
        if capacity < 4096:
            raise ShmObjectStoreError(
                f"create failed: {capacity} data bytes is no arena{fits}")
        lib = _load()
        h = lib.rts_create(path.encode(), capacity, table_slots)
        if h == -errno.EFBIG:
            raise ShmObjectStoreError(
                f"create failed: a {capacity}-byte arena is too large a "
                f"file{fits}")
        if h < 0:
            raise ShmObjectStoreError(
                f"create failed: errno {-h} ({os.strerror(-h)})")
        return cls(path, h)

    @classmethod
    def attach(cls, path: str) -> "ShmStore":
        lib = _load()
        h = lib.rts_attach(path.encode())
        if h < 0:
            raise ShmObjectStoreError(f"attach failed: errno {-h}")
        return cls(path, h)

    def close(self):
        """Detach. If zero-copy views handed out by `get` are still alive the
        mapping is left in place (it is reclaimed at process exit), matching
        plasma-client semantics where buffers outlive the client."""
        if self._h is not None:
            try:
                self._view.release()
                self._mm.close()
                self._lib.rts_detach(self._h)
            except BufferError:
                pass
            self._h = None

    # -- object ops ----------------------------------------------------------
    def create_buffer(self, object_id: bytes, size: int) -> memoryview:
        """Allocate an unsealed object; returns a writable view of its bytes.

        Contract the data plane depends on: the view (and any slice of
        it) is a C-contiguous writable memoryview over the arena mmap.
        rpc.Connection's native recv takeover uses exactly this to
        `recv()` pull chunks straight into the region
        (ctypes.from_buffer needs writable+contiguous), and RawPayload
        serving hands slices of `get` views to writev the same way —
        changing the backing to anything non-contiguous would silently
        demote bulk transfers to the buffered path."""
        off = self._lib.rts_create_object(self._h, object_id, size)
        if off == -17:  # EEXIST
            raise ObjectExistsError(object_id.hex())
        if off < 0:
            raise StoreFullError(f"alloc {size} failed: errno {-off}")
        return self._view[off:off + size]

    # Parallel-memcpy width for rts_put_iov (threads engage >= 32 MiB).
    _COPY_THREADS = min(8, os.cpu_count() or 1)

    def put(self, object_id: bytes, payloads, keep_pin: bool = False) -> None:
        """Create + copy + seal (+ drop the writer's pin) in one native
        call. `payloads` is a list of buffer-like chunks concatenated into
        the object. The whole operation runs in C with the GIL released
        (ctypes), so a multi-hundred-MB put no longer stalls the caller's
        event loop; destination pages are batch-faulted and the copy
        parallelizes for large objects. With keep_pin=False the object is
        immediately evictable unless pinned via `get` (owner pinning is
        the object-manager layer's job, as in the reference's raylet
        PinObjectIDs); keep_pin=True leaves the writer's refcount in
        place so the pin can be transferred to the node agent without an
        evictable window (see core_worker pin-transfer)."""
        import numpy as np
        n = len(payloads)
        ptrs = (ctypes.c_void_p * n)()
        lens = (ctypes.c_uint64 * n)()
        keepalive = []
        for i, p in enumerate(payloads):
            try:
                a = p if isinstance(p, np.ndarray) \
                    else np.frombuffer(p, np.uint8)
            except ValueError:      # non-contiguous exotic buffer
                a = np.frombuffer(bytes(p), np.uint8)
            if not a.flags["C_CONTIGUOUS"]:
                a = np.ascontiguousarray(a)
            keepalive.append(a)
            ptrs[i] = a.ctypes.data
            lens[i] = a.nbytes
        rc = self._lib.rts_put_iov(self._h, object_id, ptrs, lens, n,
                                   self._COPY_THREADS,
                                   1 if keep_pin else 0)
        del keepalive
        if rc == -17:  # EEXIST
            raise ObjectExistsError(object_id.hex())
        if rc < 0:
            raise StoreFullError(f"put failed: errno {-rc}")

    def seal(self, object_id: bytes) -> None:
        rc = self._lib.rts_seal(self._h, object_id)
        if rc < 0 and rc != -114:  # EALREADY ok
            raise ShmObjectStoreError(f"seal failed: errno {-rc}")

    def read_file_into(self, object_id: bytes, path: str, size: int,
                       keep_pin: bool = False) -> None:
        """Spill-restore fast path: allocate the object and read the spill
        file DIRECTLY into its arena view (readinto — one copy from the
        page cache, no intermediate Python bytes), then seal.  With
        keep_pin=False the writer pin drops at seal; keep_pin=True leaves
        it in place so the caller can transfer pins without an evictable
        window.  Raises StoreFullError/ObjectExistsError like
        create_buffer; aborts the allocation on a read failure."""
        # Open FIRST: a missing spill file must surface as
        # FileNotFoundError (-> external-tier fallback), not as whatever
        # the arena allocation would raise under memory pressure.
        with open(path, "rb", buffering=0) as f:
            buf = self.create_buffer(object_id, size)
            try:
                got = 0
                while got < size:
                    n = f.readinto(buf[got:])   # raw read: may be short
                    if not n:
                        break
                    got += n
                if got != size:
                    raise SpillTruncatedError(
                        f"spill file {path} truncated: {got}/{size} bytes")
                buf.release()
                self.seal(object_id)
                if not keep_pin:
                    self.release(object_id)
            except BaseException:
                buf.release()           # idempotent
                self.abort(object_id)
                raise

    def get(self, object_id: bytes, timeout_ms: int = 0) -> memoryview | None:
        """Returns a zero-copy readonly view, or None if absent/timeout.
        Pins the object until `release`."""
        size = ctypes.c_uint64()
        off = self._lib.rts_get(self._h, object_id, ctypes.byref(size), timeout_ms)
        if off < 0:
            return None
        return self._view[off:off + size.value].toreadonly()

    def release(self, object_id: bytes) -> None:
        self._lib.rts_release(self._h, object_id)

    def delete(self, object_id: bytes) -> bool:
        return self._lib.rts_delete(self._h, object_id) == 0

    def abort(self, object_id: bytes) -> bool:
        return self._lib.rts_abort(self._h, object_id) == 0

    def contains(self, object_id: bytes) -> bool:
        return bool(self._lib.rts_contains(self._h, object_id))

    def refcount(self, object_id: bytes) -> int:
        """Pin count of a sealed object; -1 if absent."""
        rc = self._lib.rts_refcount(self._h, object_id)
        return rc if rc >= 0 else -1

    def release_n_and_delete_if(self, object_id: bytes, n: int) -> bool:
        """Spill commit: release our n pins and delete iff no other reader
        holds a pin (atomic). False = a reader appeared; only the read pin
        was dropped and the object stays resident."""
        return self._lib.rts_release_n_and_delete_if(
            self._h, object_id, n) == 0

    def stats(self) -> dict:
        vals = [ctypes.c_uint64() for _ in range(5)]
        self._lib.rts_stats(self._h, *[ctypes.byref(v) for v in vals])
        return {
            "bytes_in_use": vals[0].value,
            "num_objects": vals[1].value,
            "num_evictions": vals[2].value,
            "bytes_evicted": vals[3].value,
            "capacity": vals[4].value,
        }

    def list_objects(self, max_ids: int = 4096) -> list[tuple]:
        """(object_id, size, refcount) snapshot of every sealed object —
        feeds the state API's `list objects`."""
        rec = 20 + 12
        buf = ctypes.create_string_buffer(rec * max_ids)
        n = self._lib.rts_list_objects(self._h, buf, max_ids)
        raw = buf.raw
        out = []
        for i in range(n):
            p = raw[i * rec:(i + 1) * rec]
            out.append((p[:20], int.from_bytes(p[20:28], "little"),
                        int.from_bytes(p[28:32], "little")))
        return out

    def list_unsealed(self, max_ids: int = 4096) -> list[tuple]:
        """(object_id, size) snapshot of allocated-but-unsealed slots —
        orphan candidates when their writer died mid-copy (reclaim with
        abort())."""
        rec = 20 + 8
        buf = ctypes.create_string_buffer(rec * max_ids)
        n = self._lib.rts_list_unsealed(self._h, buf, max_ids)
        raw = buf.raw
        return [(raw[i * rec:i * rec + 20],
                 int.from_bytes(raw[i * rec + 20:i * rec + 28], "little"))
                for i in range(n)]

    def list_evictable(self, max_ids: int = 1024) -> list[bytes]:
        buf = ctypes.create_string_buffer(20 * max_ids)
        n = self._lib.rts_list_evictable(self._h, buf, max_ids)
        raw = buf.raw
        return [raw[i * 20:(i + 1) * 20] for i in range(n)]


class ChannelClosed(ShmObjectStoreError):
    pass


class Channel:
    """Mutable single-writer multi-reader ring channel inside the arena
    (reference: python/ray/experimental/channel/shared_memory_channel.py
    backed by experimental_mutable_object_manager.cc).  A write is a
    memcpy + futex wake; a read is a futex wait + copy-out — the compiled
    graph's per-step transport.  Use `create` once (the creator's pin
    keeps it alive), `attach` from each endpoint process."""

    def __init__(self, store: "ShmStore", channel_id: bytes, offset: int,
                 attached: bool):
        self._store = store
        self._lib = store._lib
        self.channel_id = channel_id
        self._off = offset
        self._attached = attached   # holds a get() pin to drop on close

    @classmethod
    def create(cls, store: "ShmStore", channel_id: bytes, *,
               nslots: int = 8, slot_bytes: int = 1 << 20,
               nreaders: int = 1) -> "Channel":
        off = store._lib.rts_chan_init(store._h, channel_id, nslots,
                                       slot_bytes, nreaders)
        if off < 0:
            raise StoreFullError(f"channel create failed: errno {-off}")
        return cls(store, channel_id, off, attached=False)

    @classmethod
    def attach(cls, store: "ShmStore", channel_id: bytes,
               timeout_ms: int = 10_000) -> "Channel":
        size = ctypes.c_uint64()
        off = store._lib.rts_get(store._h, channel_id,
                                 ctypes.byref(size), timeout_ms)
        if off < 0:
            raise ShmObjectStoreError(
                f"channel {channel_id.hex()} not found")
        return cls(store, channel_id, off, attached=True)

    def write(self, data: bytes, timeout_ms: int = -1) -> None:
        rc = self._lib.rts_chan_write(self._store._h, self._off, data,
                                      len(data), timeout_ms)
        if rc == -32:        # EPIPE
            raise ChannelClosed(self.channel_id.hex())
        if rc == -90:        # EMSGSIZE
            raise ValueError(
                f"message of {len(data)} bytes exceeds the channel slot "
                f"size; recompile the DAG with a larger slot_bytes")
        if rc == -110:       # ETIMEDOUT
            raise TimeoutError("channel write timed out (ring full)")
        if rc < 0:
            raise ShmObjectStoreError(f"channel write: errno {-rc}")

    def read(self, reader: int = 0, timeout_ms: int = -1) -> bytes:
        """Next message for `reader` (copied out — the ring slot is reused
        as soon as we advance). Raises ChannelClosed when closed+drained."""
        moff = ctypes.c_uint64()
        mlen = ctypes.c_uint64()
        rc = self._lib.rts_chan_peek(self._store._h, self._off, reader,
                                     ctypes.byref(moff), ctypes.byref(mlen),
                                     timeout_ms)
        if rc == -32:
            raise ChannelClosed(self.channel_id.hex())
        if rc == -110:
            raise TimeoutError("channel read timed out")
        if rc < 0:
            raise ShmObjectStoreError(f"channel read: errno {-rc}")
        data = bytes(self._store._view[moff.value:moff.value + mlen.value])
        self._lib.rts_chan_advance(self._store._h, self._off, reader)
        return data

    # C ChanHdr field offsets (store.cc): magic u32@0, nslots u32@4,
    # slot_bytes u64@8, nreaders u32@16, closed u32@20, wfutex u32@24,
    # rfutex u32@28, wseq u64@32, rseq u64[8]@40; ring data at
    # align_up(sizeof(ChanHdr)=104, kAlign=64) = 128, slot stride
    # align_up(8 + slot_bytes, 64).
    _HDR_DATA_OFF = 128

    def stats(self) -> dict:
        """Unsynchronized header snapshot: write/read sequence numbers and
        ring occupancy (wseq - slowest reader).  Races with concurrent
        endpoints are benign (torn reads impossible: each field is one
        aligned word) — occupancy gauges and teardown draining use this."""
        import struct
        v = self._store._view
        nslots, = struct.unpack_from("<I", v, self._off + 4)
        slot_bytes, = struct.unpack_from("<Q", v, self._off + 8)
        nreaders, closed = struct.unpack_from("<II", v, self._off + 16)
        wseq, = struct.unpack_from("<Q", v, self._off + 32)
        rseq = list(struct.unpack_from("<8Q", v, self._off + 40))[:nreaders]
        return {"nslots": nslots, "slot_bytes": slot_bytes,
                "nreaders": nreaders, "closed": bool(closed), "wseq": wseq,
                "rseq": rseq,
                "occupancy": wseq - (min(rseq) if rseq else 0)}

    def peek_at(self, seq: int) -> bytes:
        """Copy out the message at absolute write-sequence `seq` WITHOUT
        consuming it.  Only meaningful while `seq` is still resident
        (within nslots of wseq) and the ring is quiescent — the teardown
        spill-pin drain is the only caller."""
        import struct
        st = self.stats()
        stride = (8 + st["slot_bytes"] + 63) & ~63
        base = self._off + self._HDR_DATA_OFF + (seq % st["nslots"]) * stride
        mlen, = struct.unpack_from("<Q", self._store._view, base)
        return bytes(self._store._view[base + 8:base + 8 + mlen])

    def close(self) -> None:
        """Signal EOF to all endpoints (idempotent; does not free)."""
        self._lib.rts_chan_close(self._store._h, self._off)
        if self._attached:
            self._store.release(self.channel_id)
            self._attached = False

    def destroy(self) -> None:
        """Creator-side: close + free the backing object."""
        self._lib.rts_chan_destroy(self._store._h, self.channel_id)
