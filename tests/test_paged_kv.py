"""Paged KV substrate: allocator, page-mapped prefix cache, and real reuse.

  * `PageAllocator` bookkeeping: tables, refcounted sharing, O(1) token
    accounting, the pressure-evictor hook, shortage-leaves-state-untouched
  * `write_page_rows`/`page_view` round-trip through a real model pool
  * pin semantics (the PR-5 eviction bug): LRU eviction never drops blocks
    an in-flight request admitted against, nor pages a live table still maps
  * the parity contract: a paged engine is bit-identical to the slot engine
    on prefix-free workloads — token ids AND per-request ttft / mean_tpot
    (DESIGN.md §kvcache; CI pins the same property via the harness)
  * reuse is real: on prefix-heavy workloads prefill computes exactly
    ``total prompt tokens - reported hit tokens`` on both the single-server
    session and the P/D-disaggregated fleet, with unchanged token outputs
  * the `srpt` and `cache-aware` prefill policies order as documented
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.request import Request, SLOSpec
from repro.models import build_model
from repro.policies import make_prefill
from repro.serving.clock import ManualClock
from repro.serving.disagg import DisaggSession
from repro.serving.engine import DisaggServer, EngineConfig
from repro.serving.kvcache import PageAllocator, page_view, write_page_rows
from repro.serving.prefixcache import PrefixCache
from repro.serving.session import ServeSession


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("llama3-8b-smoke").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _server(tiny_model, clock=None, **ecfg_kw):
    cfg, model, params = tiny_model
    kw = dict(max_slots=4, max_len=64, chunk_size=16)
    kw.update(ecfg_kw)
    return DisaggServer(
        model, params, EngineConfig(**kw),
        clock=clock if clock is not None else ManualClock(auto_step=1e-4),
    )


def _requests(cfg, n=4, max_out=4, seed=0, arrival_gap=0.0, shared_head=0):
    """n requests; with ``shared_head`` every prompt starts with the same
    head tokens (the prefix-heavy shape) followed by a unique tail."""
    rng = np.random.default_rng(seed)
    head = list(map(int, rng.integers(2, cfg.vocab_size, shared_head)))
    prompts = [
        head + list(map(int, rng.integers(2, cfg.vocab_size, int(rng.integers(4, 14)))))
        for _ in range(n)
    ]
    return [
        (
            Request(rid=i, arrival=arrival_gap * i, input_len=len(p),
                    output_len=max_out, slo=SLOSpec(ttft=120.0, tpot=10.0)),
            p,
        )
        for i, p in enumerate(prompts)
    ]


def _run_session(server, reqs):
    session = ServeSession(server)
    for req, prompt in reqs:
        session.submit(req, prompt)
    while session.has_work:
        session.step()
    return session


# ------------------------------------------------------------- PageAllocator
class TestPageAllocator:
    def test_alloc_link_release_lifecycle(self):
        pa = PageAllocator(page_size=4, n_pages=8)
        assert pa.free_pages == 8 and pa.used_tokens == 0
        t0 = pa.alloc_table(owner=0, n_tokens=9)  # 3 pages
        assert len(t0) == 3 and pa.free_pages == 5
        assert pa.used_tokens == 12  # page-granular, O(1)
        # a second request shares t0's first two pages, draws one fresh
        t1 = pa.alloc_table(owner=1, n_tokens=12, shared=t0[:2])
        assert t1[:2] == t0[:2] and len(t1) == 3
        assert pa.free_pages == 4 and pa.shared_links == 2
        assert pa.refcount[t0[0]] == 2
        # releasing the original owner keeps the shared pages live
        pa.release_table(0)
        assert pa.refcount[t0[0]] == 1 and t0[2] in pa.free
        pa.release_table(1)
        assert pa.free_pages == 8 and not pa.refcount and not pa.tables

    def test_shortage_returns_none_and_leaves_state_untouched(self):
        pa = PageAllocator(page_size=4, n_pages=2)
        t0 = pa.alloc_table(owner=0, n_tokens=8)
        snap = (list(pa.free), dict(pa.refcount))
        assert pa.alloc_table(owner=1, n_tokens=8) is None
        assert (list(pa.free), dict(pa.refcount)) == snap
        # sharing lowers the fresh need below the shortage
        assert pa.can_admit(8, shared=t0) and pa.can_admit(4) is False

    def test_duplicate_owner_and_excess_shared_raise(self):
        pa = PageAllocator(page_size=4, n_pages=4)
        t0 = pa.alloc_table(owner=0, n_tokens=4)
        with pytest.raises(ValueError, match="already holds"):
            pa.alloc_table(owner=0, n_tokens=4)
        with pytest.raises(ValueError, match="exceed"):
            pa.alloc_table(owner=1, n_tokens=2, shared=t0 + t0)

    def test_pressure_evictor_hook_rescues_allocation(self):
        pa = PageAllocator(page_size=4, n_pages=2)
        pa.alloc_table(owner=0, n_tokens=8)
        hoard = pa.tables[0]

        def surrender(want):
            freed = 0
            while hoard and freed < want:
                pa.release_page(hoard.pop())
                freed += 1
            return freed

        pa.evictor = surrender
        del pa.tables[0]  # the "cache" now holds the refs, not an owner
        t1 = pa.alloc_table(owner=1, n_tokens=8)
        assert t1 is not None and pa.pressure_evictions == 2


def test_gather_scatter_pages_roundtrip(tiny_model):
    """Rows written through the page table read back through the page view
    at each lane's position, and nothing else in the pool changes."""
    cfg, model, _ = tiny_model
    ps, n_pages = 4, 8
    pool = model.init_cache(n_pages, ps)
    table = jnp.array([[3, 1, 5], [0, 6, 2]])  # two requests, three pages each
    positions = jnp.array([6, 11])  # page 1 offset 2 of its table, page 2 offset 3
    rng = np.random.default_rng(1)
    rows = {
        name: jnp.asarray(
            rng.standard_normal((leaf.shape[0], 2, *leaf.shape[3:])), dtype=leaf.dtype
        )
        for name, leaf in pool.items()
    }
    before = {name: np.asarray(leaf) for name, leaf in pool.items()}
    pool2 = write_page_rows(pool, rows, table, positions)
    view = page_view(table)
    for name in pool:
        back = np.stack([np.asarray(view(layer)) for layer in pool2[name]])
        for lane, p in enumerate([6, 11]):
            np.testing.assert_array_equal(back[:, lane, p], np.asarray(rows[name][:, lane]))
        changed = np.argwhere(np.any(np.asarray(pool2[name]) != before[name], axis=(0, 3, 4)))
        assert sorted(map(tuple, changed)) == [(1, 2), (2, 3)]


# ---------------------------------------------------- pin/eviction regression
def test_eviction_never_drops_blocks_pinned_by_inflight_requests():
    """The PR-5 bug: LRU leaf eviction could evict a block an in-flight
    request's admission accounting still referenced. Pinned paths survive
    any pressure; release makes them ordinary LRU victims again."""
    cache = PrefixCache(block=4, max_blocks=3)
    held = list(range(100, 108))  # 2 blocks
    cache.admit(held, rid=7)
    # flood with one-block prompts: way over budget, all strictly younger
    for i in range(6):
        cache.admit([200 + 4 * i + j for j in range(4)])
    assert len(cache) <= 3 or cache.pinned_requests  # over budget only via pins
    assert cache.match(held) == 8  # the pinned path is fully intact
    cache.release(7)
    cache.admit([300, 301, 302, 303])  # any later admit may now evict it
    assert cache.match(held) < 8
    assert len(cache) <= 3

    # release is idempotent and unknown rids are a no-op
    cache.release(7)
    cache.release(999)


def test_eviction_never_frees_pages_mapped_by_live_tables():
    pa = PageAllocator(page_size=4, n_pages=4)
    cache = PrefixCache(block=4, max_blocks=1, pages=pa)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]
    table = pa.alloc_table(owner=0, n_tokens=8)
    cache.assign_pages(prompt, table)  # cache retains both pages
    assert pa.refcount[table[0]] == 2  # owner + cache
    # over budget (max_blocks=1) but both nodes back live-table pages:
    # eviction must refuse rather than tear KV out from under owner 0
    cache.admit([9, 10, 11, 12])
    assert cache.match(prompt) == 8
    # once the owner releases, the colder block becomes evictable
    pa.release_table(0)
    cache.admit([13, 14, 15, 16])
    assert len(cache) <= 2  # drains back toward budget as pressure allows


# ----------------------------------------------------------- parity contract
def test_paged_engine_bit_identical_to_slot_engine_prefix_free(tiny_model):
    """The acceptance pin: on a prefix-free workload (no shared heads, so
    zero page sharing) the paged engine reproduces the slot engine exactly —
    token ids AND the ManualClock latency metrics, per request."""
    reqs_a = _requests(tiny_model[0], n=5, max_out=4, seed=3, arrival_gap=0.002)
    reqs_b = copy.deepcopy(reqs_a)

    slot = _run_session(_server(tiny_model), reqs_a)
    paged = _run_session(_server(tiny_model, page_size=4), reqs_b)

    assert paged.outputs == slot.outputs
    per_s = {d["rid"]: d for d in slot.summary()["requests"]}
    per_p = {d["rid"]: d for d in paged.summary()["requests"]}
    assert per_p.keys() == per_s.keys()
    for rid in per_s:
        assert per_p[rid]["ttft"] == per_s[rid]["ttft"]
        assert per_p[rid]["mean_tpot"] == per_s[rid]["mean_tpot"]
    # and with no shared prefixes, nothing was skipped or shared
    s = paged.summary()
    assert s["prefix_cached_tokens"] == 0
    assert s["pages"]["shared_links"] == 0


def test_padded_subbatch_never_corrupts_a_live_slot(tiny_model):
    """Regression: with every slot live, a decode sub-batch smaller than its
    bucket used to pad into lane ``max_slots - 1`` — a LIVE slot — and
    overwrite that request's position-0 KV. Both substrates must match the
    scheduling-free sequential reference for every request."""
    from repro.serving.engine import reference_generate

    cfg, model, params = tiny_model
    rng = np.random.default_rng(42)
    prompts = [list(map(int, rng.integers(2, cfg.vocab_size, n)))
               for n in (12, 8, 12, 10)]  # fills all 4 slots at once
    reqs = [
        (Request(rid=i, arrival=0.0, input_len=len(p), output_len=4,
                 slo=SLOSpec(ttft=120.0, tpot=10.0)), p)
        for i, p in enumerate(prompts)
    ]
    slot = _run_session(_server(tiny_model), copy.deepcopy(reqs))
    paged = _run_session(_server(tiny_model, page_size=4), copy.deepcopy(reqs))
    for i, p in enumerate(prompts):
        ref = reference_generate(model, params, p, 4, 64)
        assert slot.outputs[i] == ref
        assert paged.outputs[i] == ref


# ------------------------------------------------------------- reuse is real
def _run_staggered(server, reqs):
    """Submit one request at a time, draining in between, so each prompt's
    KV pages have landed before the next admission probes the radix cache
    (online traffic, compressed)."""
    session = ServeSession(server)
    for req, prompt in reqs:
        session.submit(req, prompt)
        while session.has_work:
            session.step()
    return session


def test_engine_prefill_computes_exactly_prompts_minus_hits(tiny_model):
    """Prefix-heavy: prefill compute drops by exactly the reported hit
    tokens (not accounting credit — real skipped chunks), tokens unchanged."""
    reqs_a = _requests(tiny_model[0], n=6, max_out=3, seed=4, shared_head=16)
    reqs_b = copy.deepcopy(reqs_a)

    slot = _run_staggered(_server(tiny_model), reqs_a)
    paged = _run_staggered(_server(tiny_model, page_size=4), reqs_b)

    assert paged.outputs == slot.outputs  # reuse never changes tokens
    s, p = slot.summary(), paged.summary()
    total_prompt = sum(len(prompt) for _, prompt in reqs_a)
    assert s["prefill_computed_tokens"] == total_prompt  # slot mode skips nothing
    assert p["prefix_cached_tokens"] > 0
    assert p["prefill_computed_tokens"] == total_prompt - p["prefix_cached_tokens"]
    assert p["pages"]["shared_links"] > 0  # hits rode refcounted pages


def test_disagg_prefill_computes_exactly_prompts_minus_hits(tiny_model):
    """The same invariant across the P/D split: submit-time probe, pinned
    pages on the owning decode worker, prefill skips the hit tokens."""
    def _fleet(page_size=None):
        clock = ManualClock(auto_step=1e-4)
        kw = dict(page_size=page_size) if page_size else {}
        servers = [_server(tiny_model, clock=clock, **kw) for _ in range(2)]
        return DisaggSession(servers[:1], servers[1:])

    reqs_a = _requests(tiny_model[0], n=6, max_out=3, seed=5, shared_head=16)
    reqs_b = copy.deepcopy(reqs_a)

    def _drive(sess, reqs):
        # staggered online traffic: each prompt's pages land on the decode
        # worker before the next submit-time probe runs
        for req, prompt in reqs:
            sess.submit(req, prompt)
            for _ in range(5000):
                if not sess.has_work:
                    break
                sess.step()
            assert not sess.has_work
        return sess.summary()

    s = _drive(_fleet(), reqs_a)
    p = _drive(_fleet(page_size=4), reqs_b)

    total_prompt = sum(len(prompt) for _, prompt in reqs_a)
    assert s["prefill_computed_tokens"] == total_prompt
    assert p["prefix_cached_tokens"] > 0
    assert p["prefill_computed_tokens"] == total_prompt - p["prefix_cached_tokens"]
    assert p["prefix"]["hit_rate"] > 0


# ------------------------------------------------------ new prefill policies
def _queue_req(rid, input_len, output_len, cached=0, ttft=10.0):
    r = Request(rid=rid, arrival=0.0, input_len=input_len, output_len=output_len,
                slo=SLOSpec(ttft=ttft, tpot=1.0))
    r.prefix_cached_tokens = cached
    return r


def test_srpt_orders_by_total_remaining_service():
    srpt = make_prefill("srpt")
    assert srpt.name == "srpt"
    # short prompt + long generation loses to long prompt + nearly done:
    # the index is remaining prefill PLUS remaining decode, unlike sjf
    a = _queue_req(0, input_len=8, output_len=100)  # remaining 108
    b = _queue_req(1, input_len=30, output_len=2)  # remaining 32
    picked = srpt.select([a, b], t_now=0.0, mu=1e4, budget=16)
    assert picked[0][0].rid == 1
    sjf = make_prefill("sjf")
    assert sjf.select([a, b], t_now=0.0, mu=1e4, budget=16)[0][0].rid == 0

    assert srpt.select([], 0.0, 1e4, 64) == []


def test_cache_aware_prefers_cached_prefix_and_degrades_to_urgency():
    ca = make_prefill("cache-aware")
    assert ca.name == "cache-aware"
    # identical requests except one's head is already cached: fewer
    # remaining prefill tokens -> better score -> scheduled first
    cold = _queue_req(0, input_len=20, output_len=4, cached=0)
    warm = _queue_req(1, input_len=20, output_len=4, cached=16)
    assert ca.select([cold, warm], t_now=0.0, mu=1e4, budget=8)[0][0].rid == 1

    # with no cache hits anywhere the ordering IS kairos-urgency's
    ka = make_prefill("kairos-urgency")
    queue = [
        _queue_req(i, input_len=4 + 3 * i, output_len=4, ttft=5.0 + i)
        for i in range(5)
    ]
    pick_ca = [r.rid for r, _ in ca.select(queue, t_now=0.0, mu=1e4, budget=64)]
    pick_ka = [r.rid for r, _ in ka.select(queue, t_now=0.0, mu=1e4, budget=64)]
    assert pick_ca == pick_ka

    assert ca.select([], 0.0, 1e4, 64) == []
