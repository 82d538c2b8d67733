"""Slot- and page-based KV cache management for the decode engine.

Host-side allocators track which slots/pages are live and enforce the
admission cap (the paper's memory-bound decode regime); device-side helpers
read a scheduler-chosen sub-batch's cache rows and write each lane's new
row back, so the sub-batch is decoded without touching delayed requests.

A plain ``{"k", "v"}`` attention cache is updated in place: the decode step
reads the lanes' rows through a view (`slot_view` / `page_view`) and writes
only each live lane's new row (`write_slot_rows` / `write_page_rows`).
Any other cache (windowed ring, ssm, hybrid) is gathered whole by slot
(`gather_slots`), decoded and scattered back (`scatter_slots`);
`writes_rows` tells the two apart.

Two allocation substrates coexist:

  * `SlotAllocator` — the legacy contiguous layout: one ``max_len`` slot per
    request, a token-budget cap, prefix hits granted back as admission
    *credits* (accounting only, every token recomputed).
  * `PageAllocator` — fixed-size pages with per-request page tables and
    refcounted sharing (vLLM/sglang's paged-KV pattern). Matched prefix
    blocks map to *live* pages, so shared prompt heads are neither recomputed
    nor double-stored; `page_view`/`write_page_rows` are the page-table
    twins of `slot_view`/`write_slot_rows`.

See DESIGN.md §kvcache.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def cache_batch_dim(cfg: ModelConfig, leaf_name: str) -> int:
    """Axis of the slot/batch dimension for each cache leaf."""
    if cfg.family == "hybrid" and leaf_name in ("conv", "state"):
        return 2  # (Ns, per, B, ...)
    return 1  # (L, B, ...) attention / ssm / encdec


def gather_slots(cfg: ModelConfig, cache: Dict, slot_idx: jax.Array) -> Dict:
    out = {}
    for name, leaf in cache.items():
        ax = cache_batch_dim(cfg, name)
        out[name] = jnp.take(leaf, slot_idx, axis=ax)
    return out


def scatter_slots(cfg: ModelConfig, cache: Dict, sub: Dict, slot_idx: jax.Array) -> Dict:
    out = {}
    for name, leaf in cache.items():
        ax = cache_batch_dim(cfg, name)
        idx = [slice(None)] * leaf.ndim
        idx[ax] = slot_idx
        out[name] = leaf.at[tuple(idx)].set(sub[name])
    return out


def writes_rows(cache: Dict) -> bool:
    """Whether a decode step over `cache` writes only each lane's new row
    (a plain k/v attention cache) rather than whole slots."""
    return set(cache) == {"k", "v"}


def slot_view(slot_idx: jax.Array) -> Callable[[jax.Array], jax.Array]:
    """Read one layer's rows (slots, max_len, ...) of the lanes' slots:
    (B, max_len, ...). Its ops carry the ``kv_gather`` scope in a profile."""

    def read(leaf: jax.Array) -> jax.Array:
        with jax.named_scope("kv_gather"):
            return jnp.take(leaf, slot_idx, axis=0)

    return read


def write_slot_rows(cache: Dict, rows: Dict, slot_idx: jax.Array, positions: jax.Array) -> Dict:
    """Write each lane's new row ``rows[name][:, i]`` (L, B, ...) at
    ``(slot_idx[i], positions[i])`` of the (L, slots, max_len, ...) leaves,
    one single-row update per lane, in place on a donated cache. A batched
    scatter here makes the TPU compiler relayout the whole cache where the
    minor dim is 64 wide (minicpm-2b's head_dim; DESIGN.md §kvcache)."""
    out = {}
    for name, leaf in cache.items():
        r = rows[name]
        zeros = (0,) * (leaf.ndim - 3)
        for i in range(r.shape[1]):
            leaf = jax.lax.dynamic_update_slice(
                leaf, r[:, i, None, None], (0, slot_idx[i], positions[i], *zeros)
            )
        out[name] = leaf
    return out


@dataclass
class SlotAllocator:
    """Host bookkeeping: slot ids + KV token budget (admission control).

    ``credit`` on `can_admit`/`alloc` is the prefix-cache allowance
    (`repro.serving.prefixcache`): tokens whose KV is shared with an
    already-admitted prompt don't charge the budget, so a prefix-heavy
    workload admits deeper than its raw token mass suggests. The charge is
    clamped to >= 0 and remembered per slot, keeping ``release`` symmetric.
    """

    max_slots: int
    kv_cap_tokens: int

    free: List[int] = field(default_factory=list)
    live_tokens: Dict[int, int] = field(default_factory=dict)
    # running sum of live_tokens: can_admit runs per queued request per
    # step, so it must not re-sum the live set on every call
    _used: int = field(default=0, init=False, repr=False)

    def __post_init__(self) -> None:
        self.free = list(range(self.max_slots))[::-1]
        self._used = sum(self.live_tokens.values())

    @property
    def used_tokens(self) -> int:
        return self._used

    def can_admit(self, need_tokens: int, credit: int = 0) -> bool:
        charged = max(0, need_tokens - credit)
        return bool(self.free) and self._used + charged <= self.kv_cap_tokens

    def alloc(self, need_tokens: int, credit: int = 0) -> Optional[int]:
        if not self.can_admit(need_tokens, credit):
            return None
        slot = self.free.pop()
        charged = max(0, need_tokens - credit)
        self.live_tokens[slot] = charged
        self._used += charged
        return slot

    def release(self, slot: int) -> None:
        if slot in self.live_tokens:
            self._used -= self.live_tokens.pop(slot)
            self.free.append(slot)

    def snapshot(self) -> Dict:
        # the free list is part of the state: its ORDER decides which slot
        # ids future allocs hand out, and replay/failover determinism (the
        # router's restore path) depends on reproducing exactly that
        return dict(live_tokens=dict(self.live_tokens), free=list(self.free))

    def restore(self, snap: Dict) -> None:
        self.live_tokens = dict(snap["live_tokens"])
        self._used = sum(self.live_tokens.values())
        if "free" in snap:
            self.free = list(snap["free"])
        else:  # legacy snapshot without a free list: synthesize a canonical one
            live = set(self.live_tokens)
            self.free = [s for s in range(self.max_slots) if s not in live][::-1]


def page_view(page_idx: jax.Array) -> Callable[[jax.Array], jax.Array]:
    """Read one layer's pages (n_pages, page_size, ...) through the
    ``(B, pages_per_req)`` page table (pad rows and tails on the scratch
    page): (B, pages_per_req * page_size, ...), the same view `slot_view`
    gives. Rows past a request's valid length are scratch or stale, and the
    attention mask leaves them out exactly, so paged logits equal slot-mode
    logits bit for bit."""
    b, p = page_idx.shape
    flat = page_idx.reshape(-1)

    def read(leaf: jax.Array) -> jax.Array:
        with jax.named_scope("kv_gather"):
            g = jnp.take(leaf, flat, axis=0)
            return g.reshape(b, p * leaf.shape[1], *leaf.shape[2:])

    return read


def write_page_rows(pool: Dict, rows: Dict, page_idx: jax.Array, positions: jax.Array) -> Dict:
    """`write_slot_rows` through the page table: lane i's row lands at
    offset ``positions[i] % page_size`` of page
    ``page_idx[i, positions[i] // page_size]``. Decode writes only at a
    request's own position, past any shared prefix page."""
    ps = pool["k"].shape[2]
    page = jnp.take_along_axis(page_idx, (positions // ps)[:, None], axis=1)[:, 0]
    return write_slot_rows(pool, rows, page, positions % ps)


@dataclass
class PageAllocator:
    """Host bookkeeping for a fixed-size KV page pool.

    Pages are the unit of both capacity and sharing: a request's table is
    ``[shared prefix pages..., private pages...]``; shared pages bump a
    refcount instead of copying, and a page returns to the free list only
    when its last reference drops. Used-token accounting is O(1) — the page
    is the granule, so ``used_tokens`` is just occupied pages x page size.

    ``evictor`` is the prefix cache's pressure hook: when the free list
    cannot cover an allocation the allocator asks the cache to surrender
    cold, unreferenced pages (never pages a live table still maps —
    refcount > its own retain) before giving up.
    """

    page_size: int
    n_pages: int

    free: List[int] = field(default_factory=list)
    refcount: Dict[int, int] = field(default_factory=dict)
    tables: Dict[int, List[int]] = field(default_factory=dict)  # owner -> pages
    evictor: Optional[Callable[[int], int]] = None  # want_pages -> freed_pages
    # cumulative telemetry (summary()/bench rows)
    shared_links: int = field(default=0, init=False)
    pressure_evictions: int = field(default=0, init=False)

    def __post_init__(self) -> None:
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {self.n_pages}")
        # mirror SlotAllocator: pop from the tail => page 0 handed out first
        self.free = list(range(self.n_pages))[::-1]

    @property
    def free_pages(self) -> int:
        return len(self.free)

    @property
    def used_tokens(self) -> int:
        return (self.n_pages - len(self.free)) * self.page_size

    def pages_needed(self, n_tokens: int) -> int:
        return -(-max(0, n_tokens) // self.page_size)

    def can_admit(self, n_tokens: int, shared: Sequence[int] = ()) -> bool:
        """Free-list check only (no eviction attempt): ``alloc_table`` may
        still succeed where this returns False by reclaiming cache pages."""
        return self.pages_needed(n_tokens) - len(shared) <= len(self.free)

    def alloc_table(
        self, owner: int, n_tokens: int, shared: Sequence[int] = ()
    ) -> Optional[List[int]]:
        """Build ``owner``'s page table for ``n_tokens`` of KV, linking
        ``shared`` prefix pages (refcount bump) and drawing the rest fresh.
        Returns None — state untouched — if even eviction can't cover it."""
        if owner in self.tables:
            raise ValueError(f"owner {owner} already holds a page table")
        need = self.pages_needed(n_tokens)
        n_fresh = need - len(shared)
        if n_fresh < 0:
            raise ValueError(
                f"{len(shared)} shared pages exceed the {need}-page need"
            )
        if n_fresh > len(self.free) and self.evictor is not None:
            self.pressure_evictions += self.evictor(n_fresh - len(self.free))
        if n_fresh > len(self.free):
            return None
        for p in shared:
            self.refcount[p] += 1
        self.shared_links += len(shared)
        table = list(shared)
        for _ in range(n_fresh):
            p = self.free.pop()
            self.refcount[p] = 1
            table.append(p)
        self.tables[owner] = table
        return list(table)

    def retain(self, page: int) -> None:
        self.refcount[page] += 1

    def release_page(self, page: int) -> None:
        rc = self.refcount[page] - 1
        if rc:
            self.refcount[page] = rc
        else:
            del self.refcount[page]
            self.free.append(page)

    def release_table(self, owner: int) -> None:
        for p in self.tables.pop(owner, ()):
            self.release_page(p)
