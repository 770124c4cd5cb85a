"""The serving engine's host-side stores (llm/engine.py holds them; nothing
here is traced): `_PrefixCache`, page-granular prefix reuse with the state
checkpoints of a model with recurrent layers; `_KVDemoteStore`, the host
window evicted prefix pages demote into; `_KVWindow`, the bounded gather
window of a paged request's external parts."""

from __future__ import annotations

import hashlib
import os
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..exceptions import KVGatherError


class _PrefixCache:
    """Page-granular KV prefix reuse (vLLM's PagedAttention block
    sharing, Kwon et al. SOSP'23, mapped onto the paged pool): every
    FULL prompt page is keyed by the rolling hash of all tokens up to
    its end, so requests sharing a prompt prefix share the physical
    pages — skipping both the page allocation and the prefill compute
    for the shared span.

    Entries are LRU-ordered; eviction is driven by pool pressure (the
    reserve path evicts until the new request fits or the cache is dry).
    Pages are ref-counted by the engine: cache membership holds one ref
    per entry, each active request one — a page returns to the free
    list only when the last holder lets go, so evicting an entry out
    from under an in-flight request is safe.

    STATE CHECKPOINTS (`every` > 0: a model with recurrent layers).  Cached
    keys and values are then half of what a prefix left behind: the other
    half is the recurrent state after it, which is kept only at every
    `every`-th token (a row of the engine's checkpoint pool, keyed like the
    page that ends there).  An entry can be used from the last such
    boundary at or before it: `lookup` cuts the hit back to there and the
    prefill recomputes the tokens between (`recomputed` counts them).  An
    entry holds a reference to every checkpoint row at or before its own
    boundary, as it does to its pages, so evicting it frees pages and rows
    together and a row outlives every entry that could use it.  The rows
    are this cache's to hand out (`free_rows`): nothing else holds one.
    A prefill keeps the LAST `keep` boundaries it passes (`boundaries`), a
    number that follows from the rows there are and names no model: a
    re-ask needs the last boundary inside the text it shares, and a row may
    cost as much as thousands of tokens of keys and values.  Where no layer
    attends there are no pages (`insert` without a page row): an entry then
    holds rows only, and the keys, the boundaries and the eviction are as
    they are."""

    def __init__(self, page: int, tag: bytes = b"", every: int = 0,
                 rows: Sequence[int] = ()):
        self.page = page
        self.every = every
        self.free_rows: List[int] = list(rows)
        self.n_rows = len(self.free_rows)
        # boundary key -> checkpoint row, and back; row -> entries holding
        # it; entry key -> the rows it holds
        self._rows: Dict[bytes, int] = {}
        self._row_key: Dict[int, bytes] = {}
        self._row_refs: Dict[int, int] = {}
        self._held: Dict[bytes, List[int]] = {}
        self.recomputed = 0         # tokens recomputed behind a checkpoint
        self.hit_tokens = 0         # prompt tokens of the requests that hit
        self.rows_kept = 0
        self.rows_evicted = 0
        # Key namespace tag: sequence-parallel engines key their pages
        # per SP layout (tag = b"sp<degree>") so pages cached under one
        # shard→stripe mapping can never alias pages cached under
        # another — the per-shard half of "prefix-cache keys become
        # per-shard" (the other half is _Request.sp_stripes).
        self.tag = tag
        self._memo: Tuple[Any, List[bytes]] = (None, [])
        # rolling-hash key -> page ids covering the whole prefix
        self._entries: "OrderedDict[bytes, List[int]]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.hit_pages = 0          # pages whose prefill was skipped
        self.evictions = 0

    def _keys(self, prompt: Sequence[int], upto: int) -> List[bytes]:
        """Rolling hash at every page boundary 1..upto.  One admission asks
        three times (`lookup`, `boundaries`, `insert`) about one prompt:
        the last prompt's keys are kept, by the list's identity."""
        memo, keys = self._memo
        if memo is prompt and len(keys) >= upto:
            return keys[:upto]
        full = max(upto, len(prompt) // self.page)
        data = np.asarray(prompt[:full * self.page], np.int32).tobytes()
        h = hashlib.blake2b(digest_size=16)
        h.update(self.tag)
        keys, step = [], 4 * self.page
        for k in range(full):
            h.update(data[k * step:(k + 1) * step])
            keys.append(h.copy().digest())
        self._memo = (prompt, keys)
        return keys[:upto]

    def lookup(self, prompt: Sequence[int]) -> Tuple[int, List[int], int]:
        """Longest cached prefix usable by this prompt: (token count,
        page ids, checkpoint row).  Capped at S-1 tokens — the last prompt
        token's logits must be computed, so at least a one-token suffix
        always runs through prefill.  With state checkpoints the hit is cut
        back to the last boundary that kept one (row 0 with no tokens: a
        miss); without, the row is 0 and means nothing."""
        usable = (len(prompt) - 1) // self.page
        if usable <= 0:
            return 0, [], 0
        keys = self._keys(prompt, usable)
        for k in range(usable, 0, -1):
            pages = self._entries.get(keys[k - 1])
            if pages is None:
                continue
            row, found = 0, k
            if self.every:
                per = self.every // self.page
                k -= k % per
                while k and keys[k - 1] not in self._rows:
                    k -= per
                if not k:
                    break               # cached pages, but no state to go on
                row = self._rows[keys[k - 1]]
                self.recomputed += (found - k) * self.page
            self._entries.move_to_end(keys[found - 1])
            self.hits += 1
            self.hit_pages += k
            self.hit_tokens += len(prompt)
            return k * self.page, list(pages[:k]), row
        self.misses += 1
        return 0, [], 0

    def boundaries(self, prompt: Sequence[int], after: int,
                   keep: int = 0) -> List[int]:
        """The checkpoint boundaries (token counts) of `prompt` past
        `after` that its full pages cover, the last `keep` of them (0:
        all), and of those the ones no row is kept for yet."""
        if not self.every:
            return []
        full = len(prompt) // self.page * self.page
        marks = range(after + self.every, full + 1, self.every)[-keep:]
        if not marks:
            return []
        keys = self._keys(prompt, full // self.page)
        return [b for b in marks if keys[b // self.page - 1] not in self._rows]

    def hold_row(self, row: int, by: int = 1) -> None:
        """A prefill that starts from `row` holds it (`by` 1) until it has
        run (`by` -1); row 0, the state of nothing read, is nobody's."""
        if row:
            self._row_refs[row] += by
            if not self._row_refs[row]:
                self._drop_row(row)
                self.rows_evicted += 1

    def _drop_row(self, row: int) -> None:
        del self._rows[self._row_key.pop(row)], self._row_refs[row]
        self.free_rows.append(row)

    def insert(self, prompt: Sequence[int], table_row, incref,
               rows: Optional[Dict[int, int]] = None) -> None:
        """Register every full prompt page of a freshly admitted request
        (decode writes land strictly after them, so they are immutable);
        `table_row` None: there are no pages, and an entry holds rows only.
        `rows`: boundary (tokens) -> the checkpoint row (taken from
        `free_rows`) this prefill wrote for it; each new entry takes a
        reference to every row at or before its boundary, and a row no
        entry took goes back."""
        full = len(prompt) // self.page
        if full <= 0:
            for row in (rows or {}).values():
                self.free_rows.append(row)
            return
        keys = self._keys(prompt, full)
        for b, row in (rows or {}).items():
            self._rows[keys[b // self.page - 1]] = row
            self._row_key[row] = keys[b // self.page - 1]
            self._row_refs[row] = 0
            self.rows_kept += 1
        per = self.every // self.page if self.every else 0
        for k in range(1, full + 1):
            key = keys[k - 1]
            if key in self._entries:
                self._entries.move_to_end(key)
                continue
            pages = [] if table_row is None \
                else [int(p) for p in table_row[:k]]
            self._entries[key] = pages
            for p in pages:
                incref(p)
            if per:
                self._held[key] = held = [
                    self._rows[keys[j - 1]] for j in range(per, k + 1, per)
                    if keys[j - 1] in self._rows]
                for r in held:
                    self._row_refs[r] += 1
        for row in (rows or {}).values():
            if not self._row_refs[row]:
                self._drop_row(row)
                self.rows_kept -= 1

    def evict_lru(self, decref, demote=None) -> bool:
        """Drop the least-recently-used entry; True if one was dropped.
        Pages still held by active requests stay allocated (ref > 0); a
        checkpoint row whose last holder this entry was is free again.
        `demote(key, pages)` — when given — runs BEFORE the refs drop,
        so the hook can copy the page contents out of the pool while
        they are still guaranteed unrecycled (after decref the pages
        rejoin the free list and may be overwritten by any admission)."""
        if not self._entries:
            return False
        key, pages = self._entries.popitem(last=False)
        self.evictions += 1
        if demote is not None:
            demote(key, pages)
        for p in pages:
            decref(p)
        for r in self._held.pop(key, ()):
            self._row_refs[r] -= 1
            if not self._row_refs[r]:
                self._drop_row(r)
                self.rows_evicted += 1
        return True


class _KVDemoteStore:
    """Demoted prefix-cache pages: bounded host window + NVMe overflow.

    LRU-evicted prefix-cache entries land here instead of being freed
    outright: the evicted pages' contents move device -> host (a byte-
    bounded LRU window) and overflow to NVMe part files under the spill
    dir, in the external-KV part format ({"k", "v", "len"}).  A later
    request sharing the prefix PROMOTES the entry back into the pool
    (device_put + page re-alloc) instead of re-running prefill — the
    same demote-then-restore policy shape as the object store's
    arena -> NVMe spill tier, driven by the same pool-pressure signal.
    Entries are caches, never truth: any demoted entry may be dropped
    (e.g. on a disk write failure) at the cost of a re-prefill."""

    def __init__(self, byte_limit: int, spill_dir: str):
        self.byte_limit = max(0, int(byte_limit))
        self.spill_dir = spill_dir
        self._host: "OrderedDict[bytes, dict]" = OrderedDict()
        self._disk: Dict[bytes, str] = {}
        self._host_bytes = 0
        self._seq = 0
        self.demoted_pages = 0
        self.promoted_pages = 0
        self.disk_spills = 0

    def __len__(self) -> int:
        return len(self._host) + len(self._disk)

    def contains(self, key: bytes) -> bool:
        return key in self._host or key in self._disk

    def put(self, key: bytes, k_np, v_np, npages: int) -> None:
        if self.contains(key):
            return
        self._host[key] = {"k": k_np, "v": v_np, "len": int(npages)}
        self._host_bytes += k_np.nbytes + v_np.nbytes
        self.demoted_pages += int(npages)
        while self._host_bytes > self.byte_limit and self._host:
            okey, part = self._host.popitem(last=False)
            self._host_bytes -= part["k"].nbytes + part["v"].nbytes
            self._spill(okey, part)

    def _spill(self, key: bytes, part: dict) -> None:
        try:
            os.makedirs(self.spill_dir, exist_ok=True)
            self._seq += 1
            path = os.path.join(
                self.spill_dir,
                "kvdemote-%d-%d.npz" % (os.getpid(), self._seq))
            np.savez(path, k=part["k"], v=part["v"],
                     len=np.int64(part["len"]))
            self._disk[key] = path
            self.disk_spills += 1
        except OSError:
            pass    # dropped: a demoted entry is a cache, never truth

    def get(self, key: bytes) -> Optional[dict]:
        """Pop an entry for promotion ({"k","v","len"}), or None."""
        part = self._host.pop(key, None)
        if part is not None:
            self._host_bytes -= part["k"].nbytes + part["v"].nbytes
            self.promoted_pages += part["len"]
            return part
        path = self._disk.pop(key, None)
        if path is None:
            return None
        try:
            with np.load(path) as z:
                part = {"k": z["k"], "v": z["v"], "len": int(z["len"])}
        except OSError:
            return None
        finally:
            try:
                os.unlink(path)
            except OSError:
                pass
        self.promoted_pages += part["len"]
        return part

    def stats(self) -> Dict[str, Any]:
        return {"demoted_pages": self.demoted_pages,
                "promoted_pages": self.promoted_pages,
                "demoted_entries": len(self),
                "demoted_host_bytes": self._host_bytes,
                "demoted_disk_entries": len(self._disk),
                "demoted_disk_spills": self.disk_spills}


class _KVWindow:
    """Bounded host-side prefetch window over external KV parts.

    The streamed-attention path never materializes a paged request's
    context in the device pool; what it does need is the CURRENT part's
    bytes on host.  This window holds at most `capacity` parts (LRU),
    fetched through the engine's `kv_fetch` callback (the serving layer
    wires it to an object-plane get — a swarm-plane bulk pull when the
    part lives in a remote arena) and optionally warmed ahead of the
    attention step via `kv_prefetch` (async; gather overlaps compute).
    A window smaller than the part count degrades to re-fetching —
    counted, never silent (`refetches`)."""

    def __init__(self, capacity: int, fetch, prefetch=None):
        self.capacity = max(1, int(capacity))
        self._fetch = fetch
        self._prefetch = prefetch
        self._data: "OrderedDict[str, dict]" = OrderedDict()
        self._futures: Dict[str, Any] = {}
        # Recently-seen keys for refetch detection, LRU-BOUNDED: a
        # prefill shard streams thousands of one-shot context-part keys
        # that no request ever drop()s — an unbounded set would be a
        # slow leak in exactly the always-on serving process.
        self._seen: "OrderedDict[str, None]" = OrderedDict()
        self._seen_cap = max(64, 16 * self.capacity)
        self.fetches = 0
        self.refetches = 0
        self.bytes_fetched = 0
        self.wait_s = 0.0

    def _mark_seen(self, key: str) -> None:
        self._seen[key] = None
        self._seen.move_to_end(key)
        while len(self._seen) > self._seen_cap:
            self._seen.popitem(last=False)

    def _validate(self, key: str, data) -> dict:
        if not isinstance(data, dict) or "k" not in data or "v" not in data:
            raise KVGatherError(
                f"KV part {key!r} resolved to {type(data).__name__}, "
                f"expected a {{'k','v','len'}} dict")
        return data

    def _admit(self, key: str, data: dict) -> dict:
        self._data[key] = data
        self._data.move_to_end(key)
        while len(self._data) > self.capacity:
            self._data.popitem(last=False)
        return data

    def put(self, key: str, data: dict) -> None:
        """Seed a locally-produced part (chunked prefill keeps its own
        freshly published stripes hot for the next chunk)."""
        self._mark_seen(key)
        self._admit(key, data)

    def prefetch(self, items) -> None:
        """Kick async fetches for [(key, handle)] not already resident."""
        if self._prefetch is None:
            return
        for key, handle in items:
            if key in self._data or key in self._futures:
                continue
            try:
                self._futures[key] = self._prefetch(handle)
            except Exception:      # prefetch is best-effort; get() retries
                self._futures.pop(key, None)

    def get(self, key: str, handle) -> dict:
        import time as _time
        data = self._data.get(key)
        if data is not None:
            self._data.move_to_end(key)
            return data
        t0 = _time.perf_counter()
        fut = self._futures.pop(key, None)
        try:
            if fut is not None:
                data = fut.result()
            else:
                data = self._fetch(handle)
        except KVGatherError:
            raise
        except Exception as e:
            raise KVGatherError(
                f"gather of KV part {key!r} failed: "
                f"{type(e).__name__}: {e}") from e
        self.wait_s += _time.perf_counter() - t0
        data = self._validate(key, data)
        self.fetches += 1
        if key in self._seen:
            self.refetches += 1
        self._mark_seen(key)
        self.bytes_fetched += (getattr(data["k"], "nbytes", 0)
                               + getattr(data["v"], "nbytes", 0))
        return self._admit(key, data)

    def drop(self, keys) -> None:
        for k in keys:
            self._data.pop(k, None)
            self._futures.pop(k, None)
            self._seen.pop(k, None)

    def stats(self) -> Dict[str, Any]:
        return {"fetches": self.fetches, "refetches": self.refetches,
                "bytes": self.bytes_fetched, "wait_s": self.wait_s,
                "resident": len(self._data), "capacity": self.capacity}


def _default_kv_fetch(handle):
    """Engine-standalone fetch: parts passed by value ARE their data."""
    if isinstance(handle, dict):
        return handle
    raise KVGatherError(
        f"remote KV handle {type(handle).__name__} needs a kv_fetch "
        f"callback (the serving layer wires ray_tpu.get)")
