"""Fleet-wide paged KV-cache pool with shared-prefix copy-on-write reuse.

Per-request KV caches reserve ``max_len`` tokens of HBM for the whole
request lifetime, so engine occupancy is bounded by how many full-size
caches fit — the ``prefill_slots`` ceiling the ROADMAP calls out. The pool
replaces that with vLLM-style paging: one shared set of physical pages per
engine (``[n_pages, Hkv, page, D]`` K/V arrays per attention layer), a
per-request **page table** mapping logical page index -> physical page id,
and refcounted alloc/free. A request holds only the pages it has actually
written, so many partially-prefilled requests coexist where whole-cache
reservations fit few.

**Page size is a plan cell** (``kv_page`` in kernels/flash_attention/ops.py):
the VMEM-bounded tile argument of the source paper applies to page geometry
exactly as to ``bkv``, so tpu_v5e and tpu_v6e resolve different page sizes
for the same cache length and the engine reads its page from the resolved
plan.

**Shared prefixes** prefill once fleet-wide: at prefill completion a
request registers its prompt (and every full-page-boundary prefix of it)
in a *weak* registry — ``(page id, generation)`` snapshots, no refcounts —
and a later request with an identical prefix maps those pages read-only
(refcount bump) and prefills only the divergent tail. Sharing is
copy-on-write: *any* write into a page with refcount > 1 (the recipient's
first divergent token, or the donor still decoding into its shared partial
tail page) first copies the page. Registry entries are validated lazily at
lookup (page still allocated, generation unchanged since the snapshot) so
registration never pins pages and refcounts balance to zero when the fleet
drains — the invariant ``check_balanced`` asserts in the property tests.

**Admission accounting** is reservation-based: each resident request
reserves its worst-case remaining demand (pages for prompt + max new
tokens, plus ``RESERVE_SLACK`` pages of copy-on-write headroom — a request
can split at most its one shared partial tail page as recipient and its
own registered tail page as donor). ``can_admit`` admits only when the
free list covers every resident's outstanding reservation plus the
newcomer's, so a mid-flight allocation can never fail; because pages are
allocated incrementally as chunks are written, actual occupancy tracks
written tokens, not reserved caches — the occupancy unlock.

All device-side state lives in ``self.arrays`` (a pytree mirroring the
model's cache segment structure; see ``transformer.make_paged_pool``) and
is threaded *functionally* through the jitted decode/prefill programs: the
engine passes ``pool.arrays`` in, the program returns the updated arrays,
and the engine stores them back. Inside a program the pool is updated in
place: a scanned layer stack carries its stacked page arrays through the
scan, and each layer writes only its new rows and reads only its request's
pages. The decode and chunk programs donate ``pool.arrays``, so their
output reuses the input buffers and the arrays passed in are deleted: hold
no reference to them across a launch. The packed program does not donate
(a warm-up may run it on the live pool and drop the result), so it returns
a fresh copy of the pool. Host-side bookkeeping (tables, refcounts, free
list, prefix registry) is plain Python — nanoseconds per request, no jax
on the admission path.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

from repro.configs.base import ArchConfig
from repro.core.tiling import cdiv


def supports_prefix_sharing(cfg: ArchConfig) -> bool:
    """Prefix reuse requires every layer's state for positions [0, hit) to
    live in pool pages. Attention layers (windowed included — their linear
    paged cache keeps the full prefix) qualify; recurrent/SSD layers carry
    non-addressable state a prefix hit would skip computing, so hybrids
    prefill every token themselves."""
    return all(spec.mixer in ("attn", "local_attn") for spec in cfg.layers())


@dataclasses.dataclass(frozen=True)
class _PrefixEntry:
    """Weak snapshot of the pages holding one registered token prefix."""
    length: int
    pages: Tuple[int, ...]
    gens: Tuple[int, ...]


class PagedKVPool:
    """Host-side page bookkeeping + device page arrays for one engine."""

    # Copy-on-write headroom reserved per request: at most one split as a
    # prefix recipient (its shared partial tail page) plus one as a donor
    # (its registered tail page, split when its own decode write lands in a
    # now-shared page).
    RESERVE_SLACK = 2

    # Weak prefix entries kept before the oldest is evicted.
    MAX_PREFIX_ENTRIES = 512

    def __init__(self, cfg: ArchConfig, *, n_pages: int, page: int,
                 max_len: int, dtype, prefix_sharing: bool = True,
                 metrics=None, trace=None):
        from repro.models import api

        if n_pages <= 0 or page <= 0:
            raise ValueError(f"bad pool geometry: {n_pages} pages of {page}")
        self.cfg = cfg
        self.page = int(page)
        self.n_pages = int(n_pages)
        self.max_len = int(max_len)
        # Static per-request page-table length: every jitted program sees
        # the same [n_pt] table shape regardless of how many pages are
        # actually mapped (unmapped entries point at physical page 0 and
        # are position-masked inside the kernels).
        self.n_pt = cdiv(max_len, page)
        self.arrays = api.make_paged_pool(cfg, n_pages, page, dtype)
        self.prefix_sharing = bool(prefix_sharing) and \
            supports_prefix_sharing(cfg)
        self.metrics = metrics
        self._trace = trace

        self.refcount: List[int] = [0] * self.n_pages
        # Bumped when a page returns to the free list, so a stale prefix
        # entry pointing at a recycled page id fails its generation check.
        self.generation: List[int] = [0] * self.n_pages
        self._free: List[int] = list(range(self.n_pages - 1, -1, -1))
        self.tables: Dict[int, List[int]] = {}
        self._need: Dict[int, int] = {}
        self._allocs: Dict[int, int] = {}
        self._prefix: "OrderedDict[Tuple[int, ...], _PrefixEntry]" = \
            OrderedDict()

    # -- occupancy ---------------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.n_pages - len(self._free)

    def pages_needed(self, total_tokens: int) -> int:
        return cdiv(max(int(total_tokens), 1), self.page)

    def _outstanding(self) -> int:
        """Worst-case future page demand of every resident request."""
        return sum(
            max(0, self._need[r] + self.RESERVE_SLACK - self._allocs[r])
            for r in self._need)

    # -- request lifecycle -------------------------------------------------
    def can_admit(self, total_tokens: int) -> bool:
        """True when admitting a request that will write ``total_tokens``
        positions can never exhaust the pool mid-flight."""
        need = self.pages_needed(total_tokens) + self.RESERVE_SLACK
        return need + self._outstanding() <= self.free_pages

    def register_request(self, rid: int, total_tokens: int) -> None:
        if rid in self.tables:
            raise ValueError(f"request {rid} already registered")
        self.tables[rid] = []
        self._need[rid] = self.pages_needed(total_tokens)
        self._allocs[rid] = 0

    def release(self, rid: int, missing_ok: bool = False) -> int:
        """Drop every page reference ``rid`` holds; pages whose refcount
        reaches zero return to the free list (generation bumped). Raises
        ``KeyError`` on an unknown/already-released rid — a double release
        is a lifecycle bug, never silent — unless ``missing_ok`` is set:
        the eviction path (engine fault recovery, request cancel) tears
        down requests that may sit anywhere in the admission pipeline,
        including stages that never registered with the pool, and must be
        idempotent. Returns pages freed."""
        if missing_ok and rid not in self.tables:
            return 0
        table = self.tables.pop(rid)
        del self._need[rid], self._allocs[rid]
        freed = 0
        for pid in table:
            if self.refcount[pid] <= 0:
                raise RuntimeError(
                    f"double free: page {pid} (rid {rid}) has refcount "
                    f"{self.refcount[pid]}")
            self.refcount[pid] -= 1
            if self.refcount[pid] == 0:
                self.generation[pid] += 1
                self._free.append(pid)
                freed += 1
        if self.metrics is not None:
            self.metrics.record_page_free(freed)
            self.metrics.record_pool(self.used_pages, self.n_pages)
        if self._trace is not None:
            self._trace.page_free(rid, freed, self.used_pages, self.n_pages)
        return freed

    # -- page allocation / copy-on-write -----------------------------------
    def _alloc(self, rid: int) -> int:
        if not self._free:
            raise RuntimeError(
                "paged KV pool exhausted — reservation accounting should "
                "make this unreachable (can_admit gate bypassed?)")
        pid = self._free.pop()
        assert self.refcount[pid] == 0, (pid, self.refcount[pid])
        self.refcount[pid] = 1
        self._allocs[rid] += 1
        if self.metrics is not None:
            self.metrics.record_page_alloc()
        return pid

    def prepare_span(self, rid: int, start: int, length: int) -> None:
        """Make positions ``[start, start+length)`` writable by ``rid``:
        allocate pages for unmapped logical indices and copy-on-write-split
        mapped pages whose refcount exceeds one (page copies are applied to
        the device arrays here). Must run before every cache write — chunk
        prefill and each decode step alike; writes are append-only, so the
        span starts at or before the table's current end."""
        if length <= 0:
            return
        table = self.tables[rid]
        first = start // self.page
        last = (start + length - 1) // self.page
        if first > len(table):
            raise ValueError(
                f"non-contiguous write: rid {rid} start {start} but only "
                f"{len(table)} pages mapped")
        copies: List[Tuple[int, int]] = []
        fresh = 0
        for idx in range(first, last + 1):
            if idx < len(table):
                pid = table[idx]
                if self.refcount[pid] > 1:
                    dst = self._alloc(rid)
                    self.refcount[pid] -= 1
                    table[idx] = dst
                    copies.append((pid, dst))
                    if self.metrics is not None:
                        self.metrics.record_cow_split()
                    if self._trace is not None:
                        self._trace.cow_split(rid, pid, dst)
            else:
                table.append(self._alloc(rid))
                fresh += 1
        if self.metrics is not None and (fresh or copies):
            self.metrics.record_pool(self.used_pages, self.n_pages)
        if self._trace is not None and fresh:
            self._trace.page_alloc(rid, fresh, self.used_pages, self.n_pages)
        self._apply_copies(copies)

    def _apply_copies(self, copies: List[Tuple[int, int]]) -> None:
        """Copy page contents src -> dst across every layer's K/V arrays.
        Eager device ops outside jit — a handful of page-sized copies per
        split, dispatched asynchronously."""
        if not copies:
            return
        import jax
        import jax.numpy as jnp

        src = jnp.asarray([s for s, _ in copies], jnp.int32)
        dst = jnp.asarray([d for _, d in copies], jnp.int32)

        def _copy(a):
            # Page axis: 0 for seq-segment leaves [n_pages, Hkv, page, D],
            # 1 for scan-segment leaves [reps, n_pages, Hkv, page, D].
            if a.ndim == 4:
                return a.at[dst].set(a[src])
            return a.at[:, dst].set(a[:, src])

        self.arrays = jax.tree.map(_copy, self.arrays)

    # -- device views ------------------------------------------------------
    def device_table(self, rid: int):
        """The request's page table as a device array of static length
        ``n_pt`` (unmapped tail entries point at physical page 0 — masked
        positionally inside the kernels)."""
        import jax.numpy as jnp

        table = self.tables[rid]
        return jnp.asarray(
            table + [0] * (self.n_pt - len(table)), jnp.int32)

    # -- shared prefixes ---------------------------------------------------
    def lookup_prefix(self, rid: int, tokens: Sequence[int]) -> int:
        """Map the longest valid registered prefix of ``tokens`` into
        ``rid``'s (empty) page table and return its token length (0 =
        miss). The hit is capped at ``len(tokens) - 1`` so at least one
        token always prefills — the request's first-token logits must come
        from its own forward pass. Invalid entries (donor pages freed or
        recycled since the snapshot) are dropped lazily here."""
        if not self.prefix_sharing:
            return 0
        table = self.tables[rid]
        assert not table, "lookup_prefix must precede any page mapping"
        hit = 0
        n_map = 0
        toks = tuple(int(t) for t in tokens)
        for ln in sorted({e.length for e in self._prefix.values()},
                         reverse=True):
            if ln > len(toks):
                continue
            key = toks[:ln]
            entry = self._prefix.get(key)
            if entry is None:
                continue
            if not self._entry_valid(entry):
                del self._prefix[key]
                continue
            hit = min(ln, len(toks) - 1)
            if hit <= 0:
                continue
            n_map = cdiv(hit, self.page)
            for pid in entry.pages[:n_map]:
                self.refcount[pid] += 1
                table.append(pid)
            break
        if self.metrics is not None:
            self.metrics.record_prefix_lookup(hit)
        if self._trace is not None and hit:
            self._trace.prefix_hit(rid, hit, n_map)
        return hit

    def register_prefix(self, rid: int, tokens: Sequence[int]) -> None:
        """Register ``rid``'s prefilled prompt as shareable: one weak entry
        per full-page boundary plus the whole prompt. Snapshots carry page
        generations — no refcounts — so the registry never delays a free."""
        if not self.prefix_sharing:
            return
        table = self.tables[rid]
        toks = tuple(int(t) for t in tokens)
        total = len(toks)
        if total < 2:
            return  # a 1-token prefix can never be reused (hit cap)
        lengths = list(range(self.page, total, self.page)) + [total]
        for ln in lengths:
            n_p = cdiv(ln, self.page)
            if n_p > len(table):
                break
            pages = tuple(table[:n_p])
            self._prefix[toks[:ln]] = _PrefixEntry(
                length=ln, pages=pages,
                gens=tuple(self.generation[p] for p in pages))
            self._prefix.move_to_end(toks[:ln])
        while len(self._prefix) > self.MAX_PREFIX_ENTRIES:
            self._prefix.popitem(last=False)

    def _entry_valid(self, entry: _PrefixEntry) -> bool:
        return all(
            self.refcount[p] > 0 and self.generation[p] == g
            for p, g in zip(entry.pages, entry.gens))

    # -- invariants --------------------------------------------------------
    def check_balanced(self) -> None:
        """Assert the drained-pool invariant the property tests pin: with
        no resident requests, every refcount is zero and the free list
        covers the whole pool exactly once."""
        assert not self.tables, f"live page tables: {sorted(self.tables)}"
        leaked = [i for i, c in enumerate(self.refcount) if c != 0]
        assert not leaked, f"nonzero refcounts after drain: {leaked}"
        assert sorted(self._free) == list(range(self.n_pages)), (
            f"free list does not cover the pool: "
            f"{len(self._free)}/{self.n_pages}")
