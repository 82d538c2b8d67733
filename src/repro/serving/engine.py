"""Disaggregated serving engine: real JAX compute driven by core/ schedulers.

PrefillEngine owns a prefill cache per in-flight request and executes
chunked prefill steps chosen by the prefill scheduler (urgency/FCFS/...).
DecodeEngine owns the slot cache (or page pool) and updates it in place:
each step the decode scheduler (slack-guided / continuous) picks the
sub-batch, padded to a power-of-two bucket, and the jitted step, which is
handed the cache to consume, writes each lane's new K/V row into it.
Observed wall-clock step
times feed the LUT and the prefill-throughput estimator online — the same
adaptation loop the paper runs on GPUs.

Placement: a `DisaggServer` lives on one device (the default device unless
one is given). Its params, decode cache and prefill caches are committed
there, so a fleet of servers spreads over chips, and the KV handoff between
two servers on different chips is a device-to-device copy.

Engine model families: decoder-only attention archs (dense / moe / vlm).
SSM/hybrid/enc-dec serving is exercised via smoke tests + the dry-run; see
DESIGN.md §engine-scope.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import TYPE_CHECKING, Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation, annotate_function

from repro.core.lut import StepTimeLUT
from repro.core.predictor import PrefillThroughputEstimator
from repro.core.request import Request

if TYPE_CHECKING:  # import for annotation only: engine stays obs-free
    from repro.obs.events import TraceRecorder
from repro.configs.base import ModelConfig
from repro.models.model import Model, cache_struct
from repro.models.transformer import chunk_prefill_step, decode_rows, decode_step
from repro.policies import PolicySpec, make_decode, make_prefill
from repro.serving.clock import Clock, MonotonicClock
from repro.serving.kvcache import (
    PageAllocator,
    SlotAllocator,
    cache_batch_dim,
    gather_slots,
    page_view,
    scatter_slots,
    slot_view,
    write_page_rows,
    write_slot_rows,
    writes_rows,
)
from repro.serving.prefixcache import PrefixCache
from repro.serving.sampler import sample


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


# The jitted steps are module-level with the config static, so every engine
# serving one config shares one compile per shape and device (a fleet of N
# servers does not compile N copies of the same program).
_chunk_step = jax.jit(chunk_prefill_step, static_argnums=(4,))


# The decode steps and the attach writes consume the cache they are given
# (``donate_argnums``), so XLA updates it in place: the engine keeps only the
# array each call returns (DESIGN.md §kvcache). The named scopes label the
# device ops of each part of a decode step in a profiler trace (the ops'
# ``tf_op`` / ``long_name``): ``kv_gather`` the reads of the lanes' rows
# (inside ``model`` on the row path), ``model`` the trunk, ``kv_scatter``
# the writes into the cache.
@partial(jax.jit, static_argnums=(5,), donate_argnums=(3,))
def _slot_step(params, tokens, positions, cache, slot_idx, cfg: ModelConfig):
    if not writes_rows(cache):  # windowed ring, ssm, hybrid: whole slots
        with jax.named_scope("kv_gather"):
            sub = gather_slots(cfg, cache, slot_idx)
        with jax.named_scope("model"):
            logits, sub = decode_step(params, tokens, positions, cfg, sub)
        with jax.named_scope("kv_scatter"):
            return logits, scatter_slots(cfg, cache, sub, slot_idx)
    with jax.named_scope("model"):
        logits, rows = decode_rows(params, tokens, positions, cfg, cache, slot_view(slot_idx))
    with jax.named_scope("kv_scatter"):
        return logits, write_slot_rows(cache, rows, slot_idx, positions)


@partial(jax.jit, static_argnums=(5,), donate_argnums=(3,))
def _page_step(params, tokens, positions, pool, page_idx, cfg: ModelConfig):
    with jax.named_scope("model"):
        logits, rows = decode_rows(params, tokens, positions, cfg, pool, page_view(page_idx))
    with jax.named_scope("kv_scatter"):
        return logits, write_page_rows(pool, rows, page_idx, positions)


@partial(jax.jit, static_argnums=(3,), donate_argnums=(0,))
def _attach_slot(cache, kv, slot, cfg: ModelConfig):
    """Write a request's (1, max_len) prefill cache into its slot."""
    out = {}
    for name, leaf in cache.items():
        at = [0] * leaf.ndim
        at[cache_batch_dim(cfg, name)] = slot
        out[name] = jax.lax.dynamic_update_slice(leaf, kv[name], at)
    return out


@partial(jax.jit, donate_argnums=(0,))
def _attach_pages(pool, kv, dest):
    """Write a request's (1, max_len) prefill cache into the pool, block j
    into page ``dest[j]``; blocks that land nowhere go to the scratch page."""
    out = {}
    for name, leaf in pool.items():
        src = kv[name]  # (L, 1, max_len, ...)
        blocks = src.reshape(src.shape[0], dest.shape[0], leaf.shape[2], *src.shape[3:])
        out[name] = leaf.at[:, dest].set(blocks)
    return out


def _to(tree: Any, device) -> Any:
    """Commit `tree` to `device` (a no-op for None: the caller's placement
    stands). Between two chips this is the real device-to-device copy."""
    return tree if device is None else jax.device_put(tree, device)


@dataclass
class EngineConfig:
    max_slots: int = 8
    max_len: int = 256
    chunk_size: int = 64
    kv_cap_tokens: int = 1 << 16
    decode_buckets: Tuple[int, ...] = (1, 2, 4, 8)
    eos_token: int = 1
    temperature: float = 0.0
    # policy specs resolved through the repro.policies registry: a registered
    # name, or a PolicySpec carrying construction kwargs
    prefill_policy: Union[str, PolicySpec] = "kairos-urgency"
    decode_policy: Union[str, PolicySpec] = "kairos-slack"
    slo_margin: float = 0.9
    # virtual time: 1.0 => wall clock; larger stretches SLOs for slow CPUs
    time_scale: float = 1.0
    # ServeSession admission control: max requests waiting in the prefill
    # queue before submits are shed; None = unbounded (offline serve default)
    admission_queue_depth: Optional[int] = None
    # per-tenant bound on queued requests, applied on top of the global
    # bound (one tenant's burst can't monopolize admission); None = no quota
    tenant_queue_depth: Optional[int] = None
    # KV-handoff pricing, shared with the simulator through
    # `CalibratedCostModel.transfer_time` (lat + tokens * bytes / bw): the
    # session's prefill->decode admission and the disagg fleet's
    # cross-server handoff both wait this long per transfer. Units are
    # engine *virtual* seconds; defaults match the sim's cost model.
    transfer_lat: float = 0.002
    transfer_bw: float = 900e9
    kv_bytes_per_token: float = 500e3
    # paged KV (DESIGN.md §kvcache): a page size switches the decode cache
    # from one contiguous max_len slot per request to a pool of fixed-size
    # pages with per-request page tables and an engine-owned page-mapped
    # prefix cache (real reuse: matched prefix pages are linked, not
    # recomputed). None keeps the legacy slot layout. max_len must divide
    # evenly into pages; requires a plain k/v attention cache.
    page_size: Optional[int] = None
    # pool capacity in pages; None sizes it to max_slots full-length
    # requests (capacity-neutral vs slot mode)
    cache_pages: Optional[int] = None


@dataclass
class LiveRequest:
    req: Request
    tokens: List[int]  # prompt + generated
    slot: Optional[int] = None
    prefill_cache: Optional[Dict] = None
    next_logits: Optional[np.ndarray] = None
    # earliest virtual time the prefill->decode KV handoff may complete
    # (prefill_finish + CostModel.transfer_time); None until prefill is done
    transfer_ready_at: Optional[float] = None
    # paged KV: prefix pages shared from the radix cache (set at submit),
    # the engine whose pool holds them (prefill seeds its cache from it),
    # and the page table built at reserve time
    shared_pages: Optional[Tuple[int, ...]] = None
    kv_src: Optional["DecodeEngine"] = None
    page_table: Optional[List[int]] = None


@dataclass
class DecodeStepStats:
    """What one `DecodeEngine.step` did: lanes of the padded batch, each
    live lane's position, the host's virtual seconds from entry until
    the step program and the sampler were dispatched (``launch_s``) and
    then blocked until the tokens were on the host (``sync_s``), and how
    the step wrote the cache (``kv_write``): ``"row"``, each live lane's
    new K/V row in place, or ``"slot"``, the lanes' whole slots gathered
    and scattered back (windowed, ssm and hybrid caches)."""

    bucket: int
    positions: List[int]
    launch_s: float
    sync_s: float
    kv_write: str


class PrefillEngine:
    def __init__(self, model: Model, params: Dict, ecfg: EngineConfig, device=None):
        self.model, self.params, self.ecfg = model, params, ecfg
        self.device = device

    def new_cache(self) -> Dict:
        return self.model.init_cache(1, self.ecfg.max_len, self.device)

    def chunk_step(self, tokens, start, valid, cache):
        """The jitted chunk-prefill step on this engine's params: tokens
        (1, chunk_size) at offset `start`, `valid` of them real."""
        return _chunk_step(self.params, tokens, start, valid, self.model.cfg, cache)

    def _seed_cache(self, lr: LiveRequest) -> Dict:
        """Build lr's prefill cache pre-loaded with its shared prefix pages.

        This is where prefix reuse becomes real compute savings: the chunk
        loop starts at ``prefix_cached_tokens``, so the attention over the
        skipped head reads KV that was never recomputed — it is copied out
        of the source engine's page pool (positions ``[0, hit)``), exactly
        the bytes an earlier request already produced.
        """
        cache = self.new_cache()
        src = lr.kv_src
        pages = lr.shared_pages
        if src is None or not pages:
            return cache
        ps = src.page_size
        idx = jnp.asarray(pages, jnp.int32)
        for name, leaf in cache.items():
            pool = src.pool[name]  # (L, n_pages, ps, ...)
            head = jnp.take(pool, idx, axis=1)  # (L, n_shared, ps, ...)
            head = head.reshape(pool.shape[0], 1, len(pages) * ps, *pool.shape[3:])
            cache[name] = leaf.at[:, :, : len(pages) * ps].set(_to(head, self.device))
        return cache

    @partial(annotate_function, name="prefill.run_chunk")
    def run_chunk(self, lr: LiveRequest, take: int) -> Optional[np.ndarray]:
        """Prefill `take` tokens of lr; returns last logits if prompt done."""
        r = lr.req
        ecfg = self.ecfg
        if lr.prefill_cache is None:
            lr.prefill_cache = self._seed_cache(lr)
        start = r.prefix_cached_tokens + r.prefilled_tokens
        chunk = lr.tokens[start : start + take]
        pad = ecfg.chunk_size - len(chunk)
        toks = jnp.asarray([chunk + [0] * pad], jnp.int32)
        logits, lr.prefill_cache = self.chunk_step(
            toks,
            jnp.asarray([start], jnp.int32),
            jnp.asarray([len(chunk)], jnp.int32),
            lr.prefill_cache,
        )
        r.prefilled_tokens += take
        if r.prefill_done:
            return np.asarray(logits[0])
        return None


class DecodeEngine:
    def __init__(self, model: Model, params: Dict, ecfg: EngineConfig, device,
                 peek: Callable[[], float]):
        self.model, self.params, self.ecfg = model, params, ecfg
        self.device = device
        # observation-free clock read (`Clock.peek`) that times each step's
        # launch and sync into `last_step`; never advances a ManualClock
        self._peek = peek
        self.last_step: Optional[DecodeStepStats] = None
        cfg = model.cfg
        # slot ids stay the batch-lane identity in both layouts; in paged
        # mode they charge 0 tokens (the page pool is the capacity) so
        # fleet probes of alloc.free keep meaning "free decode lanes"
        self.alloc = SlotAllocator(ecfg.max_slots, ecfg.kv_cap_tokens)
        self.page_size = ecfg.page_size
        if self.page_size is not None:
            self._init_paged(cfg)
        else:
            self.pages = None
            self.prefix = None
            self.pool = None
            # +1: lane max_slots is non-allocatable scratch for pad lanes —
            # padding into a LIVE slot would overwrite its position-0 KV
            # (the paged scratch page is the same idea at page granularity)
            self.cache = model.init_cache(ecfg.max_slots + 1, ecfg.max_len, device)
            self.scratch_slot = ecfg.max_slots
        self.kv_write = "row" if writes_rows(self.cache or self.pool) else "slot"

    def _init_paged(self, cfg) -> None:
        ecfg = self.ecfg
        ps = self.page_size
        if ps < 1:
            raise ValueError(f"page_size must be >= 1, got {ps}")
        if ecfg.max_len % ps:
            raise ValueError(
                f"max_len={ecfg.max_len} must be a multiple of page_size={ps}"
            )
        leaves = set(cache_struct(cfg, 1, ps))
        if leaves != {"k", "v"}:
            raise ValueError(
                f"paged KV requires a plain k/v attention cache; family "
                f"{cfg.family!r} has leaves {sorted(leaves)}"
            )
        self.pages_per_req = ecfg.max_len // ps
        n_pages = ecfg.cache_pages or ecfg.max_slots * self.pages_per_req
        self.cache = None
        # +1: the last pool page is non-allocatable scratch for pad lanes
        # and unused page-table tails
        self.pool = self.model.init_cache(n_pages + 1, ps, self.device)
        self.scratch_page = n_pages
        self.pages = PageAllocator(page_size=ps, n_pages=n_pages)
        # the engine-owned radix cache: nodes map prefix blocks to live
        # pages in `self.pool` (contrast the session/router caches, which
        # are accounting-only). It doubles as the allocator's pressure
        # evictor via the constructor hookup.
        self.prefix = PrefixCache(block=ps, pages=self.pages)

    @property
    def paged(self) -> bool:
        return self.page_size is not None

    def reserve(self, lr: LiveRequest) -> bool:
        """Reserve decode capacity for lr without copying KV into it yet.

        The disagg fleet reserves at transfer *start* so a handoff never
        arrives at a full decode server; `attach` completes the copy. Slot
        mode charges the token budget (prefix hits granted back as a
        credit); paged mode builds the page table, linking shared prefix
        pages instead of drawing fresh ones.
        """
        r = lr.req
        if self.pages is None:
            need = r.input_len + r.output_len
            # prefix-cache credit: tokens matched at submit time share KV
            # with an earlier prompt and don't charge the budget
            slot = self.alloc.alloc(need, credit=r.prefix_hit_tokens)
            if slot is None:
                return False
            lr.slot = slot
            return True
        slot = self.alloc.alloc(0)
        if slot is None:
            return False
        shared = tuple(lr.shared_pages or ())
        if lr.kv_src is not self:
            # a foreign pool's page ids mean nothing here; the seeded
            # prefill cache carries the head bytes, attach writes them
            shared = ()
        need = min(r.input_len + r.output_len, self.ecfg.max_len)
        table = self.pages.alloc_table(slot, need, shared)
        if table is None:
            self.alloc.release(slot)
            return False
        lr.slot = slot
        lr.page_table = table
        return True

    def attach(self, lr: LiveRequest) -> None:
        """Copy lr's prefill cache (1, max_len) into its reserved slot/pages."""
        # the prefill cache lives on the prefill server's device: bring it
        # here first (a no-op when both servers share a device)
        kv = _to(lr.prefill_cache, self.device)
        lr.prefill_cache = None
        if self.pages is None:
            self.cache = _attach_slot(self.cache, kv, np.int32(lr.slot), self.model.cfg)
            return
        r = lr.req
        table = lr.page_table
        n_shared = len(lr.shared_pages or ())  # already live in this pool?
        if lr.kv_src is not self:
            n_shared = 0  # head bytes were seeded from another engine's pool
        # one shape for every request: shared and unused blocks go to scratch
        sp = self.scratch_page
        dest = [sp] * n_shared + table[n_shared:]
        dest += [sp] * (self.pages_per_req - len(dest))
        self.pool = _attach_pages(self.pool, kv, np.asarray(dest, np.int32))
        # index the landed prompt in the radix cache: later prompts sharing
        # this head link these pages instead of recomputing the KV
        self.prefix.assign_pages(lr.tokens[: r.input_len], table)

    @partial(annotate_function, name="decode.admit")
    def admit(self, lr: LiveRequest) -> bool:
        """Transfer prefill KV into decode capacity (the PD handoff)."""
        if not self.reserve(lr):
            return False
        self.attach(lr)
        return True

    def release(self, lr: LiveRequest) -> None:
        if self.prefix is not None:
            # drop the rid's radix pins whether or not it ever got a slot
            # (queue-stage cancels release before reserve succeeds)
            self.prefix.release(lr.req.rid)
        if lr.slot is not None:
            if self.pages is not None:
                self.pages.release_table(lr.slot)
                lr.page_table = None
            self.alloc.release(lr.slot)
            lr.slot = None

    def step(self, batch: List[LiveRequest], key) -> np.ndarray:
        """One decode step over the scheduler-chosen sub-batch; what it did
        is left in `last_step`."""
        ecfg = self.ecfg
        n = len(batch)
        with TraceAnnotation("decode.step"):
            t0 = self._peek()
            with TraceAnnotation("decode.launch"):
                bs = _bucket(n, ecfg.decode_buckets)
                toks = [lr.tokens[-1] for lr in batch] + [0] * (bs - n)
                pos = [lr.req.seq_len - 1 for lr in batch] + [0] * (bs - n)
                if self.pages is not None:
                    p, sp = self.pages_per_req, self.scratch_page
                    lanes = [lr.page_table + [sp] * (p - len(lr.page_table)) for lr in batch]
                else:
                    lanes = [lr.slot for lr in batch]
                logits = self._run(toks, pos, lanes, bs)
                toks_out = sample(logits, temperature=ecfg.temperature, key=key)
            t1 = self._peek()
            with TraceAnnotation("decode.sync"):
                out = np.asarray(toks_out)[:n]
            t2 = self._peek()
        scale = ecfg.time_scale
        self.last_step = DecodeStepStats(
            bs, pos[:n], (t1 - t0) * scale, (t2 - t1) * scale, self.kv_write
        )
        return out

    def _run(self, toks: List[int], pos: List[int], lanes: List, bs: int) -> jax.Array:
        """Run the jitted step on `lanes` padded to `bs` with scratch lanes
        (pad lanes write scratch only); the step consumes the cache/pool
        and the engine keeps the one it returns. Returns the logits."""
        cfg = self.model.cfg
        tokens = jnp.asarray(toks, jnp.int32)[:, None]
        positions = jnp.asarray(pos, jnp.int32)
        if self.pages is not None:
            lanes = lanes + [[self.scratch_page] * self.pages_per_req] * (bs - len(lanes))
            logits, self.pool = _page_step(
                self.params, tokens, positions, self.pool,
                jnp.asarray(lanes, jnp.int32), cfg,
            )
        else:
            lanes = lanes + [self.scratch_slot] * (bs - len(lanes))
            logits, self.cache = _slot_step(
                self.params, tokens, positions, self.cache,
                jnp.asarray(lanes, jnp.int32), cfg,
            )
        return logits

    def warmup(self) -> None:
        """Compile the decode step at every batch bucket a sub-batch of up
        to ``max_slots`` requests can land in, and the attach write. All
        lanes and the attached prefill cache go to scratch, so no live
        request's KV is touched."""
        ecfg = self.ecfg
        sizes = sorted({_bucket(n, ecfg.decode_buckets) for n in range(1, ecfg.max_slots + 1)})
        for bs in sizes:
            self._run([0] * bs, [0] * bs, [], bs).block_until_ready()
        kv = self.model.init_cache(1, ecfg.max_len, self.device)
        if self.pages is None:
            self.cache = _attach_slot(
                self.cache, kv, np.int32(self.scratch_slot), self.model.cfg
            )
        else:
            dest = np.full((self.pages_per_req,), self.scratch_page, np.int32)
            self.pool = _attach_pages(self.pool, kv, dest)
        jax.block_until_ready(self.cache or self.pool)


class DisaggServer:
    """End-to-end disaggregated server on real JAX compute, on one device.

    It runs on the CPU at smoke sizes (tests, demos) and on a TPU at a
    config's published widths (`chip_smoke.py`). Virtual time = (clock time
    since start) * time_scale: on the wall clock (`MonotonicClock`, the
    default) time_scale 1.0 makes SLOs seconds; tests drive a `ManualClock`
    so timings are deterministic.

    ``device`` pins the server: params are committed there (no copy if they
    already are), caches are allocated there, and KV arriving from a server
    on another device is copied over at the handoff. None keeps the default
    device and the caller's placement of ``params``.
    """

    def __init__(
        self,
        model: Model,
        params: Dict,
        ecfg: EngineConfig,
        clock: Optional[Clock] = None,
        trace: Optional["TraceRecorder"] = None,
        device=None,
    ):
        self.model, self.ecfg = model, ecfg
        self.device = device
        params = _to(params, device)
        self.clock: Clock = clock if clock is not None else MonotonicClock()
        # default trace sink for sessions built over this server (see
        # repro.obs): ServeSession picks it up via getattr, so an offline
        # `serve()` call traces without the caller threading a recorder
        self.trace = trace
        self.prefill = PrefillEngine(model, params, ecfg, device)
        # the engine reads whichever clock the server holds at the time (a
        # launcher may swap `self.clock` after construction)
        self.decode = DecodeEngine(model, params, ecfg, device,
                                   peek=lambda: self.clock.peek())
        self._init_sched_state()
        # transfer pricing shared with the simulator: one formula for both
        # the in-server admission handoff and the fleet's cross-server copy
        from repro.sim.costmodel import CalibratedCostModel  # no import cycle

        self.cost = CalibratedCostModel(
            transfer_lat=ecfg.transfer_lat,
            kv_bytes_per_token=ecfg.kv_bytes_per_token,
            transfer_bw=ecfg.transfer_bw,
        )
        self._t0 = self.clock.monotonic()
        self.last_session = None  # ServeSession of the most recent serve()

    def _init_sched_state(self) -> None:
        """(Re)build every piece of adaptive scheduling state — shared by
        construction and `reset_for_restart` so a restarted replica is
        indistinguishable from a freshly built one."""
        ecfg = self.ecfg
        # schedulers come from the shared policy registry — the same specs
        # (and the same classes) the simulator constructs from
        self.prefill_sched = make_prefill(ecfg.prefill_policy)
        analytic = lambda b, s: 1e-3 * (1 + 0.05 * b + s / 4096.0)
        self.lut = StepTimeLUT(analytic=analytic, seq_buckets=[16, 32, 64, 128, 256, 512])
        # slo_margin is a soft default: applied to policies that take it
        # (slack variants), dropped for those that don't (continuous)
        self.decode_sched = make_decode(
            ecfg.decode_policy, self.lut, slo_margin=ecfg.slo_margin
        )
        self.mu = PrefillThroughputEstimator(mu=2000.0)
        self._key = jax.random.key(0)

    # ------------------------------------------------------------------ time
    def _now(self) -> float:
        return (self.clock.monotonic() - self._t0) * self.ecfg.time_scale

    def peek_now(self) -> float:
        """Observation-free virtual now: the control plane's clock read.
        Unlike `_now` this never charges a `ManualClock.auto_step`, so a
        fleet controller may poll at any frequency without perturbing the
        replica's deterministic timing (see serving/clock.py)."""
        return (self.clock.peek() - self._t0) * self.ecfg.time_scale

    def reset_clock(self) -> None:
        """Re-zero virtual time (arrivals are relative to this origin).
        Virtual clocks re-zero *exactly* to their construction origin so
        timings are invariant to how many construction-time reads preceded
        the session."""
        if hasattr(self.clock, "reset"):
            origin = self.clock.reset()
            # pre-origin-contract clocks returned None from reset(); their
            # construction value was always 0.0
            self._t0 = 0.0 if origin is None else origin
        else:
            self._t0 = self.clock.monotonic()

    # --------------------------------------------------------------- restart
    def reset_for_restart(self) -> None:
        """Return the server to its just-constructed state: the live half of
        `dist/fault.py::plan_recovery`'s final step. Drops every decode slot
        (the KV is gone — survivors re-prefill restored requests), rebuilds
        the adaptive scheduler state, and re-zeroes the clock so the
        restarted replica's timing is pinnable against a fresh build."""
        ecfg = self.ecfg
        if self.decode.paged:
            # the pool, allocator, and radix cache are one consistent unit:
            # rebuild all three (the KV is gone, so are the page bindings)
            self.decode._init_paged(self.model.cfg)
        else:
            self.decode.cache = self.model.init_cache(
                ecfg.max_slots + 1, ecfg.max_len, self.device
            )
        self.decode.alloc = SlotAllocator(ecfg.max_slots, ecfg.kv_cap_tokens)
        self._init_sched_state()
        self.last_session = None
        self.reset_clock()

    def warmup(self) -> None:
        """Compile every step shape serving will run — one prefill chunk and
        each reachable decode bucket — so compilation is set-up time, not a
        stall inside the first requests' TTFT. Touches no live state."""
        ecfg = self.ecfg
        cache = self.prefill.new_cache()
        logits, _ = self.prefill.chunk_step(
            jnp.zeros((1, ecfg.chunk_size), jnp.int32),
            jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.int32),
            cache,
        )
        logits.block_until_ready()
        self.decode.warmup()

    # ------------------------------------------------------------------ serve
    def serve(self, requests: List[Tuple[Request, List[int]]]) -> Dict[int, List[int]]:
        """Serve (Request, prompt_tokens) pairs; returns rid -> output tokens.

        Requests arrive at req.arrival (virtual seconds). This is a thin
        offline wrapper over `ServeSession.run` (repro.serving.session).
        With the default unbounded `EngineConfig.admission_queue_depth`
        nothing is ever shed; if a depth IS configured, shed requests end
        in ``Phase.FAILED`` and are absent from the returned dict — inspect
        ``self.last_session.summary()`` (kept after every serve) for the
        rejection metrics.
        """
        from repro.serving.session import ServeSession  # avoid import cycle

        for req, prompt in requests:
            if req.input_len != len(prompt):
                raise ValueError(
                    f"request rid={req.rid} declares input_len={req.input_len} "
                    f"but prompt has {len(prompt)} tokens"
                )
        session = ServeSession(self)
        self.last_session = session
        return session.run(requests)


def lower_steps(model: Model, ecfg: EngineConfig, device=None) -> Dict[str, Any]:
    """Lower the engine's chunk-prefill step and its widest decode step from
    shapes alone: nothing is allocated. ``device`` may be a described device
    (`jax.experimental.topologies`), so a size can be compiled for a chip
    that is not attached, and a launcher can read a size's memory
    (``.compile().memory_analysis()``) before it commits to it.

    Returns ``{"chunk": Lowered, "decode": Lowered}``. Slot mode only: the
    launchers that size an engine serve without pages.
    """
    if ecfg.page_size is not None:
        raise ValueError("lower_steps lowers the slot-mode decode step; page_size is set")
    cfg = model.cfg
    shard = None if device is None else jax.sharding.SingleDeviceSharding(device)

    def spec(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=shard), tree
        )

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=shard)

    params = spec(model.param_struct())
    bs = _bucket(ecfg.max_slots, ecfg.decode_buckets)
    chunk = _chunk_step.lower(
        params, ints(1, ecfg.chunk_size), ints(1), ints(1), cfg,
        spec(cache_struct(cfg, 1, ecfg.max_len)),
    )
    decode = _slot_step.lower(
        params, ints(bs, 1), ints(bs),
        spec(cache_struct(cfg, ecfg.max_slots + 1, ecfg.max_len)), ints(bs), cfg,
    )
    return dict(chunk=chunk, decode=decode)


_decode_one = jax.jit(decode_step, static_argnums=(3,))


def reference_generate(
    model: Model, params: Dict, prompt: List[int], n_new: int, max_len: int, eos: int = 1
) -> List[int]:
    """Scheduling-free greedy reference: one prefill + sequential decode.

    The prompt is prefilled in one call, padded to ``max_len`` (pad KV lands
    past the valid length, which every later step masks), so one compiled
    program serves every prompt length; batch 1, no slots, pages or chunks.
    """
    cfg = model.cfg
    n = len(prompt)
    cache = model.init_cache(1, max_len)  # uncommitted: follows the params
    logits, cache = _chunk_step(
        params,
        jnp.asarray([list(prompt) + [0] * (max_len - n)], jnp.int32),
        jnp.asarray([0], jnp.int32),
        jnp.asarray([n], jnp.int32),
        cfg,
        cache,
    )
    if cfg.dtype == "float32":
        # cross-check the chunked path against the cache-free one-shot
        # prefill. Only in float32: in bfloat16 the two paths round
        # differently (attention extent, fusion), and over a full-depth
        # trunk the logit gap has no bound a fixed tolerance could state.
        one_shot, _ = model.prefill(params, dict(inputs=jnp.asarray([prompt], jnp.int32)))
        np.testing.assert_allclose(
            np.asarray(one_shot), np.asarray(logits), rtol=2e-2, atol=2e-2
        )
    out = [int(np.argmax(np.asarray(logits[0])))]
    toks = list(prompt) + out
    for _ in range(n_new - 1):
        if out[-1] == eos or len(toks) >= max_len - 1:
            break
        lg, cache = _decode_one(
            params,
            jnp.asarray([[toks[-1]]], jnp.int32),
            jnp.asarray([len(toks) - 1], jnp.int32),
            cfg,
            cache,
        )
        tok = int(np.argmax(np.asarray(lg[0])))
        out.append(tok)
        toks.append(tok)
    return out
