"""ServeEngine: continuous-batching decode over a slotted KV pool, elastic
across `resize(k)` events.

One engine tick =
  scheduler phase : policies (scale/rebalance/straggler) -> admission ->
                    prefill (whole-prompt bucketed, or page-sized CHUNKS
                    for long prompts) + KV insert
  solver phase    : ONE jitted decode step over the whole pool (every active
                    slot advances at its own position; finished/empty slots
                    are masked on the host), bracketed by the assignment's
                    begin/end_iteration ownership contract.

Two KV layouts share the scheduler and metrics:

- ``flat`` (the reference oracle): one (capacity, cache_len) row per slot.
  Admission scatters prefilled rows with a full pool copy and decode
  attends over all cache_len positions.
- ``paged``: fixed-size token pages + per-slot block tables
  (`serve.pages.PageAllocator`).  Admission writes ONLY the admitted
  request's pages (donated in-place scatter, O(pages) transfer), decode
  gathers K/V through the block table and attends only over pages live in
  this batch (table width bucketed, so work tracks live tokens instead of
  pool capacity), and long prompts prefill in chunks interleaved with
  decode ticks so one long admission cannot stall in-flight streams
  (mid-prefill slots sharing a table-width bucket batch into one forward).

With ``spec="ngram"|"draft"`` (see `serve.spec`) the solver phase turns
speculative: every slot proposes up to `spec_k` draft tokens per tick and
ONE (B, Q=spec_k+1) verify dispatch scores them all; the longest matching
draft prefix plus the model's own correction is emitted — bit-identical to
sequential greedy, up to k+1 tokens per dispatch.  Rejected tails roll back
on the host (lengths/positions) and pages allocated solely for rejected
drafts return to the free list.

With ``overlap=True`` the tick pipeline is OVERLAPPED: the decode/verify
dispatch for tick t launches first (late-binding restores join it), and
while it is in flight on device the host runs everything else — fresh
prefill dispatches, chunked-prefill chunk assembly, the disagg handoff
hook, and the staging of tick t+1's block-table image — before blocking
once for the result.  Every dispatch ships its scalar/metadata inputs
(tokens, positions, block tables, lengths, COW pairs) as ONE packed int32
transfer (`_MetaPacker`), unpacked device-side inside the jitted step.
Token streams stay bit-identical to the synchronous path (the oracle):
only the order of host work within a tick moves, never its values.

Elasticity mirrors `launch.elastic.ElasticTrainer`: `resize(k)` rebuilds the
mesh over the first min(k, n_devices) devices, re-shards params + the KV
pool with `jax.device_put` (the chunk-transfer analogue for serving state),
and swaps to a per-k cached jitted step — in-flight requests keep their KV
rows and next-token stream bit-for-bit.  Compiled artifacts are LRU-bounded
and evicted on resize so bursty scale churn cannot accumulate executables.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import set_mesh
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig
from ..faults import FaultEvent, FaultInjector
from ..launch.mesh import data_mesh
from ..models import model as M
from ..obs import MetricsRegistry, NULL_TRACER, SLOTracker, Tracer, meets_slo
from ..sharding import AxisRules
from .memory import KVMemoryManager
from .overload import (AdmissionController, CircuitBreaker,
                       DegradationLadder)
from .pages import PageAllocator, next_pow2
from .request import Request, RequestState
from .scheduler import SlotScheduler
from .spec import DraftModelDrafter, NgramDrafter, greedy_accept

# families with a flat (B, cache_len) attention cache; recurrent-state
# families (ssm/hybrid) need exact-length prefill and are follow-on work
SUPPORTED_FAMILIES = ("dense", "moe")


@dataclasses.dataclass
class TickRecord:
    tick: int
    now: float
    n_active: int
    n_workers: int
    occupancy: float
    decode_s: float
    admitted: int
    tokens_emitted: int
    admission_bytes: int = 0  # modeled device bytes written by admission
    prefill_chunks: int = 0  # chunked-prefill chunks advanced this tick
    prefill_dispatches: int = 0  # batched chunk forwards issued this tick
    page_occupancy: float = 0.0  # live fraction of the KV page pool
    spec_drafted: int = 0  # draft tokens proposed this tick
    spec_accepted: int = 0  # draft tokens verification accepted this tick
    draft_dispatches: int = 0  # device dispatches spent DRAFTING this tick
    # KV memory manager (prefix sharing / COW / eviction) deltas this tick
    shared_page_hits: int = 0  # admission pages mapped onto existing pages
    cow_breaks: int = 0  # copy-on-write share breaks fused into dispatches
    parked: int = 0  # slots preempted to host this tick
    restored: int = 0  # parked slots restored this tick
    kv_moved_bytes: int = 0  # park + restore bytes moved (host <-> device)
    shared_extra_pages: int = 0  # pages saved by sharing, end of tick
    # fault/recovery accounting (crash_worker + deadline shedding)
    crashes: int = 0  # worker-crash faults applied this tick
    retries: int = 0  # victim requests re-queued for re-execution this tick
    shed: int = 0  # requests expired this tick (retry budget / deadline)
    brownout_level: int = 0  # degradation-ladder level this tick (0 = full)
    meta_transfers: int = 0  # packed host->device metadata transfers


@dataclasses.dataclass
class ServeMetrics:
    requests: List[Request] = dataclasses.field(default_factory=list)
    ticks: List[TickRecord] = dataclasses.field(default_factory=list)
    scale_events: List[Tuple[int, int, int]] = dataclasses.field(
        default_factory=list)  # (tick, k_before, k_after)
    suspend_events: List[Tuple[int, str]] = dataclasses.field(
        default_factory=list)  # (tick, "suspend" | "resume")
    resize_moves: List[Tuple[int, int, int, int]] = dataclasses.field(
        default_factory=list)  # (tick, k_after, slots_moved, bytes_moved)
    jit_cache_sizes: Dict[str, int] = dataclasses.field(default_factory=dict)
    kv_stats: Dict[str, Any] = dataclasses.field(default_factory=dict)
    fault_events: List[Tuple[int, str, Any]] = dataclasses.field(
        default_factory=list)  # (tick, kind, target)
    recovery_events: List[Tuple[int, int, int]] = dataclasses.field(
        default_factory=list)  # (crash_tick, recovery_ticks, n_victims)
    # overload control: SLO targets stamped by the engine (so goodput is
    # computed from the request records, independent of tracker windows),
    # ladder transitions (tick, level, level_name) and breaker transitions
    brownout_events: List[Tuple[int, int, str]] = dataclasses.field(
        default_factory=list)
    breaker_events: List[Tuple[int, str]] = dataclasses.field(
        default_factory=list)
    slo_ttft: Optional[float] = None
    slo_tpot: Optional[float] = None
    wall_s: float = 0.0

    def to_registry(self, registry: Optional[MetricsRegistry] = None
                    ) -> MetricsRegistry:
        """Re-back the serve telemetry onto an `obs.MetricsRegistry`: every
        quantity `summarize()` reports becomes a typed counter / gauge /
        histogram under ``serve.*`` — the same data, pluggable into any
        exporter.  `summarize()` itself reads from this registry."""
        reg = registry if registry is not None else MetricsRegistry()
        done = [r for r in self.requests if r.state is RequestState.FINISHED]
        reg.gauge("serve.requests_total").set(len(self.requests))
        reg.gauge("serve.requests_finished").set(len(done))
        h_ttft = reg.histogram("serve.ttft_s")
        h_tpot = reg.histogram("serve.tpot_s")
        # queue delay is arrival -> FIRST admission only; time a parked /
        # handed-off request spends waiting to be re-admitted accumulates
        # in the separate handoff-delay histogram (they used to conflate)
        h_qdel = reg.histogram("serve.queue_delay_s")
        h_hoff = reg.histogram("serve.handoff_delay_s")
        requeued = 0
        for r in done:
            if r.ttft() is not None:
                h_ttft.observe(r.ttft())
            if r.tpot() is not None:
                h_tpot.observe(r.tpot())
            if r.t_admitted is not None:
                h_qdel.observe(r.t_admitted - r.arrival_time)
            if r.handoff_delay > 0:
                h_hoff.observe(r.handoff_delay)
                requeued += 1
        reg.gauge("serve.requeued").set(requeued)
        # backpressure + SLO attainment: rejections are terminal refusals
        # at admission (never queued), counted apart from EXPIRED sheds;
        # goodput scores FINISHED requests against the stamped targets
        # (per-request overrides win) straight from their timestamps
        reg.gauge("serve.requests_rejected").set(
            sum(1 for r in self.requests
                if r.state is RequestState.REJECTED))
        if self.slo_ttft is not None or self.slo_tpot is not None:
            met = sum(1 for r in done if meets_slo(
                r.ttft(), r.tpot(),
                self.slo_ttft if r.slo_ttft is None else r.slo_ttft,
                self.slo_tpot if r.slo_tpot is None else r.slo_tpot))
            reg.gauge("serve.slo_met").set(met)
            reg.gauge("serve.goodput").set(met / len(done) if done else 0.0)
        reg.counter("serve.tokens_generated").inc(
            sum(r.n_generated for r in done))
        per_tick = {
            "serve.tokens_emitted": "tokens_emitted",
            "serve.admission_bytes": "admission_bytes",
            "serve.prefill_chunks": "prefill_chunks",
            "serve.prefill_dispatches": "prefill_dispatches",
            "serve.draft_dispatches": "draft_dispatches",
            "serve.spec_drafted": "spec_drafted",
            "serve.spec_accepted": "spec_accepted",
            "serve.shared_page_hits": "shared_page_hits",
            "serve.cow_breaks": "cow_breaks",
            "serve.parked": "parked",
            "serve.restored": "restored",
            "serve.kv_moved_bytes": "kv_moved_bytes",
            "serve.retries_total": "retries",
            "serve.shed_requests": "shed",
            "serve.crashes": "crashes",
            "serve.meta_transfers": "meta_transfers",
        }
        for metric, field in per_tick.items():
            reg.counter(metric).inc(
                sum(getattr(t, field) for t in self.ticks))
        reg.counter("serve.solver_dispatches").inc(
            sum(1 for t in self.ticks if t.tokens_emitted))
        reg.counter("serve.resize_moved_bytes").inc(
            sum(m[3] for m in self.resize_moves))
        # one recovery = one crash's victim cohort fully re-admitted or shed
        reg.counter("serve.recoveries").inc(len(self.recovery_events))
        h_rec = reg.histogram("serve.recovery_ticks")
        for _, rticks, _ in self.recovery_events:
            h_rec.observe(rticks)
        h_occ = reg.histogram("serve.occupancy")
        h_pocc = reg.histogram("serve.page_occupancy")
        h_shx = reg.histogram("serve.shared_extra_pages")
        h_dec = reg.histogram("serve.decode_s")
        for t in self.ticks:
            h_occ.observe(t.occupancy)
            h_pocc.observe(t.page_occupancy)
            h_shx.observe(t.shared_extra_pages)
            if t.decode_s > 0:
                h_dec.observe(t.decode_s)
        reg.gauge("serve.n_ticks").set(len(self.ticks))
        reg.gauge("serve.wall_s").set(self.wall_s)
        return reg

    def summarize(self) -> Dict[str, Any]:
        reg = self.to_registry()
        cnt = lambda n: int(reg.counter(n).value)  # noqa: E731
        hist = lambda n: reg.histogram(n)  # noqa: E731
        pct = (lambda h, q: float(np.percentile(h.values, q))
               if h.values else None)
        done = int(reg.gauge("serve.requests_finished").value)
        toks = cnt("serve.tokens_generated")
        emitted = cnt("serve.tokens_emitted")
        # per-dispatch efficiency charges the drafter's own model dispatches
        # too (draft-model speculation pays 2 dispatches/tick; ngram 1)
        draft_disp = cnt("serve.draft_dispatches")
        dispatches = cnt("serve.solver_dispatches") + draft_disp
        drafted = cnt("serve.spec_drafted")
        accepted = cnt("serve.spec_accepted")
        mean = lambda n: hist(n).mean or 0.0  # noqa: E731
        return {
            "requests_finished": done,
            "requests_total": int(reg.gauge("serve.requests_total").value),
            "tokens_generated": toks,
            "tokens_per_s": toks / self.wall_s if self.wall_s else 0.0,
            "ttft_p50_s": pct(hist("serve.ttft_s"), 50),
            "ttft_p99_s": pct(hist("serve.ttft_s"), 99),
            "tpot_p50_s": pct(hist("serve.tpot_s"), 50),
            "tpot_p99_s": pct(hist("serve.tpot_s"), 99),
            "queue_delay_p50_s": pct(hist("serve.queue_delay_s"), 50),
            "queue_delay_p99_s": pct(hist("serve.queue_delay_s"), 99),
            "handoff_delay_p50_s": pct(hist("serve.handoff_delay_s"), 50),
            "handoff_delay_p99_s": pct(hist("serve.handoff_delay_s"), 99),
            "requeued_total": int(reg.gauge("serve.requeued").value),
            "occupancy_mean": mean("serve.occupancy"),
            "page_occupancy_mean": mean("serve.page_occupancy"),
            "admission_bytes_total": cnt("serve.admission_bytes"),
            "prefill_chunks_total": cnt("serve.prefill_chunks"),
            "prefill_dispatches_total": cnt("serve.prefill_dispatches"),
            "meta_transfers_total": cnt("serve.meta_transfers"),
            # speculative decode: useful work per decode dispatch
            "decode_dispatches": int(dispatches),
            "draft_dispatches": int(draft_disp),
            "tokens_per_dispatch": (emitted / dispatches if dispatches
                                    else 0.0),
            "spec_drafted_total": drafted,
            "spec_accepted_total": accepted,
            "spec_acceptance_rate": (accepted / drafted if drafted else None),
            # KV memory manager: sharing / COW / eviction / migration
            "shared_page_hits_total": cnt("serve.shared_page_hits"),
            "cow_breaks_total": cnt("serve.cow_breaks"),
            "parked_total": cnt("serve.parked"),
            "restored_total": cnt("serve.restored"),
            "kv_moved_bytes_total": cnt("serve.kv_moved_bytes"),
            "shared_extra_pages_mean": mean("serve.shared_extra_pages"),
            "resize_moved_bytes_total": cnt("serve.resize_moved_bytes"),
            # fault tolerance: crash recoveries, re-executions, load shed
            "recoveries": cnt("serve.recoveries"),
            "retries_total": cnt("serve.retries_total"),
            "shed_requests": cnt("serve.shed_requests"),
            "crashes_total": cnt("serve.crashes"),
            "recovery_ticks_mean": hist("serve.recovery_ticks").mean,
            "recovery_events": [list(e) for e in self.recovery_events],
            # overload control: backpressure + SLO goodput + brownouts
            "rejected_requests": int(
                reg.gauge("serve.requests_rejected").value),
            "slo_ttft_target": self.slo_ttft,
            "slo_tpot_target": self.slo_tpot,
            "slo_met": (int(reg.gauge("serve.slo_met").value)
                        if (self.slo_ttft is not None
                            or self.slo_tpot is not None) else None),
            "goodput": (float(reg.gauge("serve.goodput").value)
                        if (self.slo_ttft is not None
                            or self.slo_tpot is not None) else None),
            "brownout_events": [list(e) for e in self.brownout_events],
            "breaker_events": [list(e) for e in self.breaker_events],
            "brownout_level_max": max(
                (t.brownout_level for t in self.ticks), default=0),
            "kv_stats": dict(self.kv_stats),
            "jit_cache_sizes": dict(self.jit_cache_sizes),
            "n_ticks": len(self.ticks),
            "scale_events": [list(e) for e in self.scale_events],
            "suspend_events": [list(e) for e in self.suspend_events],
            "wall_s": self.wall_s,
        }


def _lru_get(cache: Dict, key, build: Callable[[], Any], cap: int,
             tracer: Optional[Tracer] = None, label: str = ""):
    """Move-to-end LRU over an insertion-ordered dict.  A miss is a jit
    retrace/compile: when a tracer is attached it gets an instant
    ``jit.miss`` event and the build runs under a ``jit.build`` span, so
    cache churn (e.g. resize storms evicting executables) is visible in
    the trace instead of showing up as a mysteriously slow phase."""
    if key in cache:
        cache[key] = cache.pop(key)
    elif tracer is not None and tracer.enabled:
        tracer.instant("jit.miss", track="jit", label=label, key=str(key))
        tracer.count("serve.jit_misses")
        with tracer.span("jit.build", track="jit", label=label,
                         key=str(key)):
            cache[key] = build()
    else:
        cache[key] = build()
    while len(cache) > cap:
        cache.pop(next(iter(cache)))
    return cache[key]


class _MetaPacker:
    """Pinned-style host staging for per-dispatch metadata: every scalar /
    small-array input of a dispatch (next tokens, positions, block tables,
    lengths, COW pairs, chunk offsets, write ids) is copied into ONE
    contiguous int32 staging buffer and shipped as ONE host->device
    transfer; the jitted step slices its views back out device-side.
    Buffers are persistent (the pinned-buffer idiom) and rotate through a
    small ring so a buffer is never rewritten while an earlier async
    dispatch's transfer could still reference it — a tick issues at most a
    handful of packs (decode/verify + a few prefill groups)."""

    RING = 8
    __slots__ = ("_bufs", "_i")

    def __init__(self):
        self._bufs = [np.empty(256, np.int32) for _ in range(self.RING)]
        self._i = 0

    def pack(self, arrays) -> jnp.ndarray:
        total = 0
        for a in arrays:
            total += a.size
        self._i = (self._i + 1) % self.RING
        buf = self._bufs[self._i]
        if buf.size < total:
            buf = self._bufs[self._i] = np.empty(next_pow2(total), np.int32)
        off = 0
        for a in arrays:
            n = a.size
            buf[off:off + n] = np.ravel(a)
            off += n
        return jnp.asarray(buf[:total])


class ServeEngine:
    """Continuous-batching serving engine with Chicle-style elasticity."""

    def __init__(self, cfg: ModelConfig, *, capacity: int = 8,
                 cache_len: int = 64, prefill_bucket: int = 16,
                 n_workers: int = 1, policies: Sequence = (),
                 slots_per_chunk: int = 2, max_admit_per_tick: int = 4,
                 seed: int = 0, params: Optional[Any] = None,
                 tenant_weights: Optional[Dict[str, float]] = None,
                 clock: Optional[Any] = None,
                 kv_layout: str = "flat", page_size: int = 8,
                 chunked_prefill: Optional[bool] = None,
                 prefill_chunk: Optional[int] = None,
                 paged_impl: str = "xla",
                 prefix_share: Optional[bool] = None,
                 evict: Optional[bool] = None,
                 spec: str = "off", spec_k: int = 4,
                 drafter: Optional[Any] = None,
                 draft_cfg: Optional[ModelConfig] = None,
                 draft_params: Optional[Any] = None,
                 debug_checks: bool = False,
                 decode_enabled: bool = True,
                 overlap: bool = False,
                 fault_injector: Optional[FaultInjector] = None,
                 retry_backoff: int = 1,
                 retry_jitter: bool = True,
                 slo_ttft: Optional[float] = None,
                 slo_tpot: Optional[float] = None,
                 slo_window: int = 64,
                 tenant_rate: Optional[Any] = None,
                 tenant_burst: Optional[Any] = None,
                 queue_cap: Optional[int] = None,
                 admission: Optional[AdmissionController] = None,
                 brownout: str = "off",
                 ladder: Optional[DegradationLadder] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 tracer: Optional[Tracer] = None,
                 max_cached_meshes: int = 2, max_cached_fns: int = 16):
        if cfg.family not in SUPPORTED_FAMILIES:
            raise NotImplementedError(
                f"ServeEngine supports flat-KV families {SUPPORTED_FAMILIES}; "
                f"got {cfg.family!r} (recurrent-state prefill is follow-on)")
        if kv_layout not in ("flat", "paged"):
            raise ValueError(f"kv_layout must be 'flat' or 'paged', "
                             f"got {kv_layout!r}")
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1 decode slot, "
                             f"got {capacity}")
        if n_workers < 1:
            raise ValueError(f"n_workers must be >= 1, got {n_workers}")
        if cache_len < 1:
            raise ValueError(f"cache_len must be >= 1 token, got {cache_len}")
        if spec not in ("off", "ngram", "draft"):
            raise ValueError(f"spec must be 'off', 'ngram' or 'draft', "
                             f"got {spec!r}")
        if brownout not in ("off", "auto"):
            raise ValueError(f"brownout must be 'off' or 'auto', "
                             f"got {brownout!r}")
        if kv_layout != "paged":
            if prefix_share:
                raise ValueError("prefix_share requires kv_layout='paged' "
                                 "(sharing maps block-table pages)")
            if evict:
                raise ValueError("evict requires kv_layout='paged' "
                                 "(parking moves pages, not rows)")
        # phase tracing: NULL_TRACER's disabled fast path keeps the default
        # un-traced run bit-identical and a single attribute check slower
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.cfg = cfg
        self.capacity = capacity
        self.cache_len = cache_len
        self.prefill_bucket = prefill_bucket
        self.kv_layout = kv_layout
        self.page_size = page_size
        self.paged_impl = paged_impl
        # KV memory manager defaults: both ON for the paged layout (sharing
        # and eviction never change token streams, only bytes moved)
        self.prefix_share = (kv_layout == "paged" if prefix_share is None
                             else bool(prefix_share))
        self.evict = (kv_layout == "paged" if evict is None else bool(evict))
        self.chunked_prefill = (kv_layout == "paged" if chunked_prefill is None
                                else chunked_prefill)
        self.prefill_chunk = prefill_chunk or prefill_bucket
        self.max_cached_meshes = max(1, max_cached_meshes)
        self.max_cached_fns = max(1, max_cached_fns)
        if self.chunked_prefill and kv_layout != "paged":
            raise ValueError("chunked_prefill requires kv_layout='paged' "
                             "(chunks append to pages in place)")
        if kv_layout == "paged":
            if page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {page_size}")
            if cache_len < page_size:
                raise ValueError(
                    f"zero-page budget: cache_len {cache_len} < page_size "
                    f"{page_size} gives every slot 0 KV pages")
            if cache_len % page_size or prefill_bucket % page_size:
                raise ValueError("cache_len and prefill_bucket must be "
                                 "multiples of page_size")
            if self.prefill_chunk % page_size:
                raise ValueError("prefill_chunk must be a multiple of "
                                 "page_size")
        self.devices = list(jax.devices())
        self.rng = np.random.default_rng(seed)
        self.params = (params if params is not None
                       else M.init_params(cfg, jax.random.key(seed)))
        # overload control (everything defaults OFF = bit-identical to an
        # engine without these knobs): token-bucket + bounded-queue
        # admission lives in the scheduler; the SLO tracker scores
        # finishes; the degradation ladder and circuit breaker act in tick
        if admission is None and (tenant_rate is not None
                                  or queue_cap is not None):
            admission = AdmissionController(
                tenant_rate=tenant_rate, tenant_burst=tenant_burst,
                queue_cap=queue_cap,
                drain_rate=float(max_admit_per_tick))
        self.slo = (SLOTracker(ttft_target=slo_ttft, tpot_target=slo_tpot,
                               window=slo_window, tracer=self.tracer)
                    if (slo_ttft is not None or slo_tpot is not None)
                    else None)
        self.ladder = (ladder if ladder is not None
                       else DegradationLadder() if brownout == "auto"
                       else None)
        self.breaker = breaker
        self.scheduler = SlotScheduler(
            capacity, n_workers=n_workers, slots_per_chunk=slots_per_chunk,
            policies=policies, max_admit_per_tick=max_admit_per_tick,
            seed=seed, tenant_weights=tenant_weights, admission=admission,
            tracer=self.tracer)
        # external simulation clock (cluster orchestrator); None = wall clock
        self._clock = clock
        self.suspended = False
        self.debug_checks = debug_checks
        # decode_enabled=False makes this a PREFILL-ONLY pool half: the
        # solver phase never runs, freshly prefilled slots sit in _by_slot
        # until a DisaggEngine extract()s them for the decode pool
        self.decode_enabled = bool(decode_enabled)
        if not self.decode_enabled and kv_layout != "paged":
            raise ValueError("decode_enabled=False (a disagg prefill pool) "
                             "requires kv_layout='paged' — the handoff "
                             "moves pages")

        # speculative decode: each slot proposes spec_k drafts per tick and
        # ONE (B, Q=spec_k+1) verify dispatch scores them all; the drafter
        # never affects the token stream, only the acceptance rate
        self.spec_k = int(spec_k) if (spec != "off" or drafter is not None) \
            else 0
        if self.spec_k <= 0:
            self.drafter = None
            self.spec_k = 0
        elif drafter is not None:
            self.drafter = drafter
        elif spec == "draft":
            if draft_params is None:
                # a freshly initialized draft model shares nothing with the
                # target: the plumbing runs end-to-end but acceptance is ~0,
                # making speculation pure overhead until trained (or
                # distilled) draft params are supplied
                import warnings
                warnings.warn(
                    "spec='draft' without draft_params uses a randomly "
                    "initialized draft model — acceptance will be ~0 and "
                    "speculation slower than spec='off'; pass draft_params "
                    "(a trained/distilled draft model) or use spec='ngram'",
                    stacklevel=2)
                if draft_cfg is None:
                    draft_cfg = dataclasses.replace(
                        cfg, name=cfg.name + "-draft",
                        num_layers=max(1, cfg.num_layers // 2))
            self.drafter = DraftModelDrafter(draft_cfg or cfg, draft_params,
                                             seed=seed)
        else:  # spec == "ngram"
            self.drafter = NgramDrafter()
        if self.drafter is not None:
            # drafters are pluggable objects: hand them the engine tracer so
            # their own jit caches emit jit.miss events onto the same trace
            self.drafter.tracer = self.tracer

        self.max_pages_per_slot = cache_len // page_size
        if kv_layout == "paged":
            n_pages = capacity * self.max_pages_per_slot + 1  # +1: null page
            self.mem: Optional[KVMemoryManager] = KVMemoryManager(
                n_pages, page_size, prefix_share=self.prefix_share,
                tracer=self.tracer)
            self.pages: Optional[PageAllocator] = self.mem.pages
            self.blocks = M.init_paged_cache(cfg, n_pages,
                                             page_size)["blocks"]
            self.k_pos = None
        else:
            self.mem = None
            self.pages = None
            cache = M.init_cache(cfg, capacity, cache_len, per_slot=True)
            self.blocks = cache["blocks"]
            self.k_pos = cache["k_pos"]
        self._pool_bytes = int(sum(np.prod(v.shape) * v.dtype.itemsize
                                   for v in jax.tree.leaves(self.blocks)))
        # host-side per-slot stream state
        self.next_tok = np.zeros((capacity, 1), np.int32)
        # overlapped tick pipeline: launch the decode/verify dispatch first,
        # do the rest of the tick's host work while it is in flight, block
        # once at the end.  Streams stay bit-equal to the sync oracle.
        self.overlap = bool(overlap)
        # host work to run INSIDE the overlap window (the DisaggEngine
        # hangs its handoff extraction here so park gathers from the
        # prefill pool hide behind the decode pool's in-flight dispatch)
        self.overlap_hook: Optional[Callable[[], Any]] = None
        self._meta = _MetaPacker()
        self._tick_meta = 0  # packed metadata transfers this tick
        # block-table image staged in the previous tick's overlap window;
        # consumed (or discarded on any page/membership change) at bind
        self._plan: Optional[Dict[str, Any]] = None
        # rolling KV-stats snapshot: tick deltas are measured against the
        # PREVIOUS tick's end, so parks/restores driven between ticks (e.g.
        # a cluster lease shrink) still land in the next tick's record
        self._kv_prev = self.mem.stats() if self.mem is not None else None
        self._by_slot: Dict[int, Request] = {}
        self._prefilling: Dict[int, Tuple[Request, int]] = {}  # slot -> (req, off)
        # fault tolerance: injector polled at the top of every tick; crash
        # victims wait host-side in _retrying (ready_tick, req) until their
        # exponential backoff expires, then re-queue through the scheduler
        self.fault_injector = fault_injector
        self.retry_backoff = max(1, int(retry_backoff))
        # jittered backoff desynchronizes multi-victim re-admission (no
        # thundering herd); drawn from the engine RNG, deterministic per
        # seed, and timing-only (streams stay bit-equal to the oracle)
        self.retry_jitter = bool(retry_jitter)
        self._retrying: List[Tuple[int, Request]] = []
        self._slow_factors: Dict[int, float] = {}
        self._recovering: List[Dict[str, Any]] = []
        self._tick_faults = {"crashes": 0, "retries": 0, "shed": 0}
        self.metrics = ServeMetrics()
        self.metrics.slo_ttft = slo_ttft
        self.metrics.slo_tpot = slo_tpot
        # the ladder degrades/restores these; the base values are the
        # level-0 configuration recovery walks back to
        self._base_spec_k = self.spec_k
        self._base_drafter = self.drafter
        self._base_prefill_chunk = self.prefill_chunk
        self._tick = 0
        self._t0: Optional[float] = None
        self._last_stats: Dict = {}

        # per-k compiled artifacts: k_mesh -> (mesh, rules, decode_fn);
        # dependent jit caches are keyed by k_mesh too and evicted with it
        self._k_cache: Dict[int, Tuple[Mesh, AxisRules, Any]] = {}
        self._prefill_cache: Dict[Tuple[int, int], Any] = {}
        self._insert_cache: Dict[Tuple[int, int, int], Any] = {}
        self._chunk_cache: Dict[Tuple[int, int, int], Any] = {}
        self._restore_cache: Dict[Tuple[int, int], Any] = {}
        self.k = 0
        self.mesh: Optional[Mesh] = None
        self.resize(n_workers)

    # --- elasticity -------------------------------------------------------
    def _k_mesh(self, k: int) -> int:
        return max(1, min(k, len(self.devices)))

    @property
    def n_active_slots(self) -> int:
        """Slots currently consuming KV: decoding + mid-prefill."""
        return len(self._by_slot) + len(self._prefilling)

    def _slot_workers(self) -> Tuple[List[int], Dict[int, int]]:
        """Snapshot the live slots and their current worker assignment."""
        live = sorted(set(self._by_slot) | set(self._prefilling))
        return live, {s: self.scheduler.worker_of_slot(s) for s in live}

    def _record_resize_moves(self, k: int, live: List[int],
                             before: Dict[int, int]) -> None:
        """Page-granular migration accounting for one scale event: only the
        pages of slots whose worker changed count as moved state."""
        moved = [s for s in live
                 if self.scheduler.worker_of_slot(s) != before[s]]
        if self.pages is not None:
            nbytes = sum(self.pages.n_pages_of(s)
                         for s in moved) * self._page_bytes
        else:  # flat rows: a moved slot drags its whole cache row
            nbytes = len(moved) * (self._pool_bytes // self.capacity)
        self.metrics.resize_moves.append(
            (self._tick, k, len(moved), int(nbytes)))

    def _build(self, km: int):
        mesh = data_mesh(self.devices[:km])
        rules = AxisRules(mesh)
        cfg = self.cfg

        # the decode/verify steps take their scalar inputs as ONE packed
        # int32 metadata vector (see `_MetaPacker`) and slice the views
        # back out here, inside the trace — each layout's component widths
        # are recoverable from the meta length (plus the static draft span
        # Q for the paged verify, where (Q, table_width) would otherwise
        # alias in the length)
        cap = self.capacity

        if self.kv_layout == "paged":
            impl = self.paged_impl
            # without prefix sharing no page can ever reach refcount 2, so
            # the fused COW copy is dead work — trace it out entirely
            use_cow = self.prefix_share

            def unpack(meta, q):
                w = meta.shape[0] // cap - q - 4
                tok = meta[:cap * q].reshape(cap, q)
                pos = meta[cap * q: cap * (q + 1)]
                table = meta[cap * (q + 1): cap * (q + 1 + w)].reshape(cap, w)
                lengths = meta[cap * (q + 1 + w): cap * (q + 2 + w)]
                cow_src = meta[cap * (q + 2 + w): cap * (q + 3 + w)]
                cow_dst = meta[cap * (q + 3 + w):]
                return tok, pos, table, lengths, cow_src, cow_dst

            def decode(params, blocks, meta):
                tok, pos, table, lengths, cow_src, cow_dst = unpack(meta, 1)
                logits, new_cache = M.paged_decode_step(
                    cfg, params, {"blocks": blocks}, tok, pos, table,
                    lengths, rules=rules, impl=impl,
                    cow=(cow_src, cow_dst) if use_cow else None)
                nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
                return nxt, new_cache["blocks"]

            def verify(params, blocks, meta, q):
                tok, pos, table, lengths, cow_src, cow_dst = unpack(meta, q)
                logits, new_cache = M.paged_verify_step(
                    cfg, params, {"blocks": blocks}, tok, pos, table,
                    lengths, rules=rules, impl=impl,
                    cow=(cow_src, cow_dst) if use_cow else None)
                return (jnp.argmax(logits, -1).astype(jnp.int32),
                        new_cache["blocks"])

            return (mesh, rules, jax.jit(decode, donate_argnums=(1,)),
                    jax.jit(verify, donate_argnums=(1,),
                            static_argnums=(3,)))

        def decode(params, blocks, k_pos, meta):
            tok = meta[:cap].reshape(cap, 1)
            pos = meta[cap:]
            cache = {"blocks": blocks, "k_pos": k_pos}
            logits, new_cache = M.decode_step(cfg, params, cache, tok, pos,
                                              rules=rules)
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            return nxt, new_cache["blocks"], new_cache["k_pos"]

        def verify(params, blocks, k_pos, meta):
            q = meta.shape[0] // cap - 2
            tok = meta[:cap * q].reshape(cap, q)
            pos = meta[cap * q: cap * (q + 1)]
            n_new = meta[cap * (q + 1):]
            cache = {"blocks": blocks, "k_pos": k_pos}
            logits, new_cache = M.verify_step(cfg, params, cache, tok, pos,
                                              n_new, rules=rules)
            return (jnp.argmax(logits, -1).astype(jnp.int32),
                    new_cache["blocks"], new_cache["k_pos"])

        return (mesh, rules, jax.jit(decode, donate_argnums=(1, 2)),
                jax.jit(verify, donate_argnums=(1, 2)))

    def _cache_sharding(self, mesh: Mesh):
        """Flat pool: shard the slot (batch) dim over data when capacity
        divides, else replicate (GSPMD would pad unevenly)."""
        ndev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        batch = "data" if self.capacity % ndev == 0 else None
        return (NamedSharding(mesh, P(None, batch)),
                NamedSharding(mesh, P(batch)))

    def _paged_sharding(self, mesh: Mesh):
        """Paged pool (nb, n_pages, ps, kv, hd): shard the page dim when it
        divides the mesh, else replicate."""
        ndev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        n_pages = jax.tree.leaves(self.blocks)[0].shape[1]
        page = "data" if n_pages % ndev == 0 else None
        return NamedSharding(mesh, P(None, page))

    def _evict_stale(self) -> None:
        """Drop compiled prefill/insert/chunk fns whose mesh was evicted."""
        live = set(self._k_cache)
        for cache in (self._prefill_cache, self._insert_cache,
                      self._chunk_cache, self._restore_cache):
            for key in [k for k in cache if k[0] not in live]:
                del cache[key]

    def _stamp_cache_sizes(self) -> None:
        self.metrics.jit_cache_sizes = {
            "k_cache": len(self._k_cache),
            "prefill_cache": len(self._prefill_cache),
            "insert_cache": len(self._insert_cache),
            "chunk_cache": len(self._chunk_cache),
            "restore_cache": len(self._restore_cache),
        }

    def resize(self, k: int) -> None:
        """Elastic scale event: k logical workers, mesh over the first
        min(k, n_devices) devices.  KV state and in-flight requests carry
        over; only the sharding and the compiled step change.  Stale
        compiled artifacts beyond `max_cached_meshes` are evicted here.

        The migration cost is PAGE-GRANULAR: only pages owned by slots
        whose worker assignment changed count as moved state (recorded in
        `metrics.resize_moves`) — the slot-chunk rebalance itself is
        minimal-churn, so a scale event costs O(moved pages), the serving
        twin of training's chunk transfers, not O(pool).  (When the device
        mesh itself changes, the single pool array is re-laid-out by
        `device_put`; the accounting tracks the algorithmic cost that a
        per-worker page-pool runtime would pay.)"""
        if k < 1:
            raise ValueError(
                f"resize(k) needs at least one worker, got k={k}; to stop "
                f"serving use suspend(), not a zero-worker resize")
        if self.scheduler.n_workers != k:
            live, before = self._slot_workers()
            self.scheduler.set_workers(k)
            self._record_resize_moves(k, live, before)
        km = self._k_mesh(k)
        mesh, rules, _, _ = _lru_get(self._k_cache, km,
                                     lambda: self._build(km),
                                     self.max_cached_meshes,
                                     self.tracer, "k_mesh")
        self._evict_stale()
        if mesh is not self.mesh:
            self.params = jax.device_put(self.params,
                                         NamedSharding(mesh, P()))
            if self.kv_layout == "paged":
                self.blocks = jax.device_put(self.blocks,
                                             self._paged_sharding(mesh))
            else:
                blocks_s, row_s = self._cache_sharding(mesh)
                self.blocks = jax.device_put(self.blocks, blocks_s)
                self.k_pos = jax.device_put(self.k_pos, row_s)
            if self.drafter is not None:
                # speculation state moves with the pool (draft params for
                # the draft-model drafter; host-only drafters no-op)
                self.drafter.on_resize(mesh, rules)
        self.k, self.mesh, self.rules = k, mesh, rules
        self._stamp_cache_sizes()

    # --- prefill ----------------------------------------------------------
    def _bucket(self, n: int) -> int:
        b = self.prefill_bucket
        return min(((n + b - 1) // b) * b, self.cache_len)

    def _page_bucket(self, n_pages: int) -> int:
        """Block-table width bucket: next power of two, so the per-width
        decode/chunk retrace count stays logarithmic in cache_len."""
        return min(next_pow2(max(n_pages, 1)), self.max_pages_per_slot)

    def _n_bucket(self, n: int) -> int:
        """Batch-size bucket for grouped chunk forwards: next power of two
        (capped at capacity), the same trick the admission path uses to
        bound per-batch-size retraces."""
        return min(next_pow2(max(n, 1)), self.capacity)

    def _prefill_fn(self, bucket: int):
        km = self._k_mesh(self.k)
        cfg, rules, cache_len = self.cfg, self.rules, self.cache_len
        paged = self.kv_layout == "paged"

        def build():
            def prefill(params, tokens, true_len):
                # paged rows stay at bucket length (chopped into pages by
                # the insert scatter); flat rows pad out to cache_len
                logits, cache = M.prefill(
                    cfg, params, tokens, rules=rules, remat=False,
                    cache_len=bucket if paged else cache_len,
                    true_len=true_len)
                nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
                if paged:
                    return nxt, cache["blocks"]["k"], cache["blocks"]["v"]
                return nxt, cache["blocks"], cache["k_pos"]

            return jax.jit(prefill)

        return _lru_get(self._prefill_cache, (km, bucket), build,
                        self.max_cached_fns, self.tracer, "prefill")

    def _insert_fn(self, n: int, bucket: int):
        """Paged admission scatter: writes ONLY the admitted requests' pages
        into the (donated) pools — O(pages) transfer, no pool copy."""
        km = self._k_mesh(self.k)
        ps = self.page_size
        bpp = bucket // ps

        def build():
            def insert(blocks, rows_k, rows_v, page_ids):
                def chop(rows):  # (nb, n, bucket, ...) -> (nb, n*bpp, ps, ...)
                    return rows.reshape(rows.shape[0], n * bpp, ps,
                                        *rows.shape[3:])
                return {"k": blocks["k"].at[:, page_ids].set(chop(rows_k)),
                        "v": blocks["v"].at[:, page_ids].set(chop(rows_v))}

            return jax.jit(insert, donate_argnums=(0,))

        return _lru_get(self._insert_cache, (km, n, bucket), build,
                        self.max_cached_fns, self.tracer, "insert")

    def _restore_fn(self, n_pages: int):
        """Scatter a parked sequence's host pages back into the (donated)
        pools — the restore twin of `_insert_fn`, but the rows arrive
        already paged so no chop is needed.  O(pages) transfer."""
        km = self._k_mesh(self.k)

        def build():
            def restore(blocks, rows_k, rows_v, page_ids):
                return {"k": blocks["k"].at[:, page_ids].set(rows_k),
                        "v": blocks["v"].at[:, page_ids].set(rows_v)}

            return jax.jit(restore, donate_argnums=(0,))

        return _lru_get(self._restore_cache, (km, n_pages), build,
                        self.max_cached_fns, self.tracer, "restore")

    def _chunk_fn(self, chunk: int, table_width: int, n: int):
        km = self._k_mesh(self.k)
        cfg, rules, impl = self.cfg, self.rules, self.paged_impl

        def build():
            def step(params, blocks, tokens, meta):
                nb = tokens.shape[0]
                offset = meta[:nb]
                chunk_end = meta[nb: 2 * nb]
                table = meta[2 * nb:].reshape(nb, -1)
                last, new_cache = M.paged_prefill_chunk(
                    cfg, params, {"blocks": blocks}, tokens, offset,
                    chunk_end, table, rules=rules, impl=impl)
                nxt = jnp.argmax(last[:, -1], -1).astype(jnp.int32)
                return nxt, new_cache["blocks"]

            return jax.jit(step, donate_argnums=(1,))

        return _lru_get(self._chunk_cache, (km, chunk, table_width, n),
                        build, self.max_cached_fns, self.tracer, "chunk")

    def _pack_meta(self, *arrays) -> jnp.ndarray:
        """ONE host->device transfer for a dispatch's scalar/metadata
        inputs (counted per tick as `meta_transfers`); the jitted step
        slices the components back out device-side."""
        self._tick_meta += 1
        return self._meta.pack(arrays)

    @property
    def _page_bytes(self) -> int:
        """Device bytes of one K+V page across the block stack."""
        leaf = jax.tree.leaves(self.blocks)[0]  # (nb, N, ps, kv, hd)
        nb, _, ps, kv, hd = leaf.shape
        return 2 * nb * ps * kv * hd * leaf.dtype.itemsize

    def _insert(self, slots, blocks_rows, k_pos_rows) -> None:
        """Flat-layout scatter of prefilled rows into the pool at `slots`
        (one batched scatter per admit group — a full pool copy; the paged
        layout replaces this with `_insert_fn`)."""
        idx = jnp.asarray(slots, jnp.int32)
        # rows (nb, n, cache_len, ...) scatter into pool (nb, cap, cache_len, ...)
        self.blocks = jax.tree.map(
            lambda pool, rows: pool.at[:, idx].set(rows),
            self.blocks, blocks_rows)
        self.k_pos = self.k_pos.at[idx].set(k_pos_rows)

    def _release(self, req: Request, now: float) -> None:
        """Finish a request: return its pages (paged) and its slot."""
        if self.mem is not None and req.slot is not None:
            self.mem.release_slot(req.slot)
        self.scheduler.release(req, now)
        if self.slo is not None:
            # score the finish against its targets (per-request overrides
            # win); the tracker traces slo.miss and feeds the ladder
            self.slo.observe(rid=req.rid, tenant=req.tenant,
                             ttft=req.ttft(), tpot=req.tpot(),
                             ttft_target=req.slo_ttft,
                             tpot_target=req.slo_tpot)

    # --- eviction: park / restore (page-granular preemption) --------------
    def park(self, slot: int, *, requeue: bool = True) -> int:
        """Preempt the decoding request in `slot`: gather ONLY its live
        pages to host memory (one O(pages) device->host copy, no
        re-prefill on return), free its pages + slot, and re-queue the
        request (state PARKED) for a later `restore` through admission.
        requeue=False leaves the request out of the queue — the disagg
        handoff path (`extract`) moves it to another engine instead.
        Returns the bytes moved."""
        if self.mem is None:
            raise RuntimeError("park requires kv_layout='paged'")
        req = self._by_slot.pop(slot, None)
        if req is None:
            raise KeyError(f"slot {slot} has no decoding request")
        with self.tracer.span("park", rid=req.rid, slot=slot):
            table = self.pages.table(slot)
            idx = jnp.asarray(np.asarray(table, np.int32))
            host = {name: np.asarray(arr[:, idx])
                    for name, arr in self.blocks.items()}
            seq = self.mem.park(req.rid, slot, host,
                                int(self.scheduler.pool.pos[slot]),
                                int(self.next_tok[slot, 0]),
                                prompt=req.prompt)
            self.scheduler.pool.free(slot)
            req.slot = None
            req.state = RequestState.PARKED
            req.t_parked = self._now()  # handoff-delay clock starts
            if requeue:
                self.scheduler.submit(req)  # rejoins tenant queue
        return seq.nbytes

    def extract(self, slot: int) -> Tuple[Request, Any]:
        """Disaggregation handoff, prefill side: park `slot`'s request
        WITHOUT re-queueing it and pop the parked payload.  The caller
        moves (request, ParkedSeq) to the decode pool's `inject`."""
        req = self._by_slot[slot]
        self.park(slot, requeue=False)
        return req, self.mem.take_parked(req.rid)

    def inject(self, req: Request, seq: Any) -> None:
        """Disaggregation handoff, decode side: adopt a foreign parked
        sequence (produced by another engine's `extract`) and queue its
        request — the next admission restores it through the normal
        parked-restore path (one scatter, zero re-prefill, bit-exact)."""
        if self.mem is None:
            raise RuntimeError("inject requires kv_layout='paged'")
        if req.prompt_len + req.max_new_tokens > self.cache_len:
            raise ValueError(
                f"request {req.rid}: prompt {req.prompt_len} + max_new "
                f"{req.max_new_tokens} exceeds cache_len {self.cache_len}")
        self.mem.adopt(seq)
        self.scheduler.submit(req)
        self.metrics.requests.append(req)

    def park_excess(self, n: int) -> int:
        """Park up to `n` decoding slots, lowest priority first (latest
        admitted within a priority) — the cluster lease-shrink hook.
        Returns total bytes moved to host."""
        moved = 0
        for _ in range(max(0, n)):
            victim = self._pick_victim()
            if victim is None:
                break
            moved += self.park(victim)
        return moved

    def _pick_victim(self) -> Optional[int]:
        """Lowest-priority, most-recently-admitted decoding slot."""
        cands = [(req.priority, -(req.t_admitted or 0.0), slot)
                 for slot, req in self._by_slot.items()]
        return min(cands)[2] if cands else None

    def _preempt_for(self, incoming: Request) -> bool:
        """Scheduler hook: admit `incoming` over a STRICTLY lower-priority
        in-flight decode by parking the victim (KV to host, no work lost).
        Returns True when a slot was freed."""
        if self.mem is None or not self.evict:
            return False
        victim = self._pick_victim()
        if victim is None:
            return False
        if self._by_slot[victim].priority >= incoming.priority:
            return False
        self.park(victim)
        return True

    def _restore_slot(self, req: Request) -> int:
        """Re-admit a parked request: pages re-matched against the prefix
        index where possible (restore re-sharing), ONE scatter of the
        unshared payload, decode state restored — the stream continues
        bit-for-bit with zero prefill compute.  Returns bytes moved."""
        with self.tracer.span("restore", rid=req.rid, slot=req.slot):
            plan = self.mem.restore(req.rid, req.slot)
            seq, table = plan.seq, plan.table
            nb = min(next_pow2(max(len(table), 1)), self.max_pages_per_slot)
            # pad rows AND re-shared pages route to the null page: only the
            # unshared payload is written (AdmitPlan's write-id trick)
            ids = np.zeros(nb, np.int32)
            ids[: len(table)] = plan.write_ids
            rows = {}
            for name, arr in seq.pages.items():
                pad = np.zeros(
                    (arr.shape[0], nb - arr.shape[1]) + arr.shape[2:],
                    arr.dtype)
                rows[name] = np.concatenate([arr, pad], axis=1)
            self.blocks = self._restore_fn(nb)(
                self.blocks, jnp.asarray(rows["k"]), jnp.asarray(rows["v"]),
                self._pack_meta(ids))
            req.state = RequestState.DECODING
            self.next_tok[req.slot, 0] = seq.next_tok
            self.scheduler.pool.pos[req.slot] = seq.live_tokens
            self._by_slot[req.slot] = req
        return plan.moved_bytes

    # --- fault injection + crash recovery ---------------------------------
    def _backoff_ticks(self, retries: int) -> int:
        """Exponential crash-retry backoff, jittered by uniform(0.5, 1.5)
        from the engine RNG: victims of one crash spread their re-admission
        over distinct ticks instead of stampeding back as one cohort.
        Deterministic per seed; at least one tick either way."""
        base = self.retry_backoff * (1 << (retries - 1))
        if self.retry_jitter:
            return max(1, int(round(base * float(self.rng.uniform(0.5,
                                                                  1.5)))))
        return base

    def apply_fault(self, ev: FaultEvent) -> None:
        """Route one injected fault.  Serve-level kinds only: revoke_lease
        is cluster scope and handoff_drop is disagg scope — both are
        ignored here so one FaultPlan can span all three layers."""
        if ev.kind == "worker_crash":
            self.crash_worker(ev.target if ev.target is None
                              else int(ev.target))
        elif ev.kind == "worker_slow":
            w = self.k - 1 if ev.target is None else int(ev.target)
            self.set_worker_slow(w, ev.factor)

    def set_worker_slow(self, worker: int, factor: float) -> None:
        """Straggler injection: `worker`'s modeled task time scales by
        `factor` until cleared with factor 1.0 — feeds the same per-worker
        timing stats `StragglerMitigationPolicy` watches."""
        if factor == 1.0:
            self._slow_factors.pop(worker, None)
        else:
            self._slow_factors[worker] = float(factor)
        self.metrics.fault_events.append((self._tick, "worker_slow", worker))

    def crash_worker(self, worker: Optional[int] = None) -> List[Request]:
        """Abrupt zero-grace loss of one logical worker (default: the
        highest-id live worker): every KV page and slot resident on it is
        gone.  Victim requests (mid-prefill and mid-decode alike) restart
        from the prompt — greedy decode is deterministic, so a re-executed
        stream is bit-equal to a fault-free run's — re-queueing through
        RETRYING with exponential backoff, or shedding to EXPIRED once the
        retry budget is blown.  The pool shrinks to the survivors via the
        normal `resize` path (a k=1 crash cold-starts a replacement worker:
        all resident KV was already dropped).  Returns the victims."""
        sched = self.scheduler
        if worker is None:
            worker = sched.n_workers - 1
        if not 0 <= worker < sched.n_workers:
            raise ValueError(f"crash_worker: worker {worker} not in live "
                             f"set 0..{sched.n_workers - 1}")
        now = self._now()
        self.metrics.fault_events.append((self._tick, "worker_crash", worker))
        self._tick_faults["crashes"] += 1
        with self.tracer.span("recovery.crash", track="faults",
                              worker=worker):
            victims: List[Request] = []
            for slot in sched.slots_of_worker(worker):
                req = self._by_slot.pop(slot, None)
                if req is None:
                    ent = self._prefilling.pop(slot, None)
                    req = ent[0] if ent is not None else None
                if req is None:
                    continue
                # the dead worker's pages are unreachable: free them and
                # invalidate prefix-index entries that pointed at them
                # (host-parked payloads are self-contained copies and
                # survive untouched)
                if self.mem is not None:
                    self.mem.release_slot(slot)
                sched.pool.free(slot)
                req.slot = None
                victims.append(req)
            for req in victims:
                req.generated = []
                req.t_first_token = None
                req.retries += 1
                if req.retries > req.max_retries:
                    self._shed(req, now, reason="retries")
                else:
                    req.state = RequestState.RETRYING
                    ready = self._tick + self._backoff_ticks(req.retries)
                    self._retrying.append((ready, req))
                    self._tick_faults["retries"] += 1
                    self.tracer.count("serve.retries_total")
            if victims:
                self._recovering.append(
                    {"tick": self._tick, "n": len(victims),
                     "pending": {r.rid: r for r in victims}})
            self.resize(max(1, self.k - 1))
            # logical workers renumber on shrink: factors past the new k
            # die with their worker ids
            self._slow_factors = {w: f for w, f in self._slow_factors.items()
                                  if w < self.k}
        return victims

    def _shed(self, req: Request, now: float, *, reason: str) -> None:
        """Terminal load shed: EXPIRED, never re-queued.  Any parked host
        payload is dropped (not leaked), any held slot/pages released."""
        if self.mem is not None and self.mem.has_parked(req.rid):
            self.mem.take_parked(req.rid)
        if req.slot is not None:
            if self.mem is not None:
                self.mem.release_slot(req.slot)
            self.scheduler.pool.free(req.slot)
            req.slot = None
        req.state = RequestState.EXPIRED
        req.t_finished = now
        self._tick_faults["shed"] += 1
        self.tracer.instant("shed", track="faults", rid=req.rid,
                            reason=reason)
        self.tracer.count("serve.shed_requests")

    def _requeue_retries(self) -> None:
        """Move backoff-expired crash victims back into the admission
        queue; their original arrival time keeps them near the front of
        their tenant's FCFS queue."""
        due = [ent for ent in self._retrying if ent[0] <= self._tick]
        if not due:
            return
        self._retrying = [ent for ent in self._retrying
                          if ent[0] > self._tick]
        with self.tracer.span("recovery.requeue", track="faults",
                              n=len(due)):
            for _, req in due:
                req.state = RequestState.QUEUED
                self.scheduler.submit(req)

    def _shed_expired(self, now: float) -> None:
        """Deadline-based shedding: queued or retrying requests past their
        deadline are EXPIRED instead of (re-)admitted.  In-flight decodes
        run to completion — admission is the shedding point."""
        for req in self.scheduler.shed_expired(now):
            self._shed(req, now, reason="deadline")
        keep: List[Tuple[int, Request]] = []
        for rdy, req in self._retrying:
            if req.deadline is not None \
                    and now - req.arrival_time > req.deadline:
                self._shed(req, now, reason="deadline")
            else:
                keep.append((rdy, req))
        self._retrying = keep

    # --- graceful degradation (brownout ladder) ---------------------------
    def _apply_degradation(self, level: int) -> None:
        """Reconfigure for a ladder level.  A pure function of (base
        config, level) — walking back down restores the exact level-0
        configuration.  Every action trades service *quality* (latency,
        batching efficiency), never stream content: greedy decode at any
        level is bit-equal to an oracle engine statically configured the
        same way."""
        k = self._base_spec_k
        drafter = self._base_drafter
        chunk = self._base_prefill_chunk
        if level >= 1:  # spec_shrink: halve the draft depth
            k = max(1, k // 2) if k else 0
        if level >= 2:  # spec_off: drop speculative drafting entirely
            drafter = None
        if level >= 3 and self.chunked_prefill:
            chunk = self.page_size  # chunk_cap: minimum legal chunk width
        restored = drafter is not None and self.drafter is None
        self.drafter = drafter
        self.spec_k = k if drafter is not None else 0
        self.prefill_chunk = chunk
        if restored and self.mesh is not None:
            # resize() skips a detached drafter; re-sync its device state
            # with the current mesh on the way back up
            drafter.on_resize(self.mesh, self.rules)

    def _brownout_actions(self, now: float) -> None:
        """Per-tick work for the ladder's top levels (the lower levels are
        pure reconfiguration applied once per transition)."""
        lvl = self.ladder.level
        sched = self.scheduler
        if lvl >= 4 and self.mem is not None and self.evict:
            # park_low: free a slot for a strictly higher-priority waiter
            # even before the pool is full (admission's preempt hook only
            # fires once it is)
            heads = [q[0] for q in sched._queues.values()
                     if q and q[0].arrival_time <= now]
            if heads:
                top = max(h.priority for h in heads)
                victim = self._pick_victim()
                if victim is not None \
                        and self._by_slot[victim].priority < top:
                    self.park(victim)
                    self.tracer.instant("degrade.park", track="overload",
                                        slot=victim)
        if lvl >= 5 and self.slo is not None \
                and self.slo.ttft_target is not None:
            # shed_late: a queued request already past its TTFT target is
            # a guaranteed miss — shed it instead of serving dead weight.
            # Parked/retrying work is exempt (it holds restorable state).
            late = sched.pop_older_than(
                now, self.slo.ttft_target,
                pred=lambda r: (r.state is RequestState.QUEUED
                                and r.retries == 0))
            for r in late:
                self._shed(r, now, reason="brownout")

    def _settle_recoveries(self) -> None:
        """Close recovery windows: a crash's victim cohort is recovered
        when every victim has re-emitted its first token or been shed;
        the window's tick count is the recovery latency."""
        still: List[Dict[str, Any]] = []
        for rec in self._recovering:
            rec["pending"] = {
                rid: r for rid, r in rec["pending"].items()
                if not (r.state is RequestState.EXPIRED
                        or (r.n_generated > 0
                            and r.state in (RequestState.DECODING,
                                            RequestState.FINISHED)))}
            if rec["pending"]:
                still.append(rec)
            else:
                rticks = self._tick - rec["tick"]
                self.metrics.recovery_events.append(
                    (rec["tick"], rticks, rec["n"]))
                self.tracer.instant("recovery.done", track="faults",
                                    crash_tick=rec["tick"], ticks=rticks)
        self._recovering = still

    def _start_decoding(self, req: Request, nxt: int, now: float) -> None:
        """Common PREFILL -> DECODING (or immediate finish) transition once
        the first token exists."""
        req.generated.append(nxt)
        req.t_first_token = now
        if req.done():  # max_new_tokens == 1: prefill's token ends it
            self._release(req, now)
            return
        req.state = RequestState.DECODING
        self.next_tok[req.slot, 0] = nxt
        self.scheduler.pool.pos[req.slot] = req.prompt_len
        self._by_slot[req.slot] = req

    def _do_prefill(self, admitted: Sequence[Request],
                    defer: Optional[List] = None) -> int:
        """Prefill this tick's admissions, one batched forward per shared
        bucket length, and insert their KV into the pool.  PARKED requests
        restore their host-parked pages instead (no model forward at all);
        fresh paged admissions map their longest indexed prompt prefix onto
        existing physical pages and scatter only the rest.  Long prompts in
        paged+chunked mode defer to `_advance_prefills` instead.  Returns
        modeled admission bytes written to the device KV pool.

        When `defer` is given (the overlapped tick's prep window) the
        dispatches launch async and their settle + PREFILL->DECODING
        transitions are pushed onto it as (handle, [(row, request), ...])
        for `_settle_prefills` to finish after the window closes."""
        direct: List[Request] = []
        nbytes = 0
        for r in admitted:
            if self.mem is not None and self.mem.has_parked(r.rid):
                nbytes += self._restore_slot(r)
            # submit() already rejected prompt+max_new > cache_len, so the
            # chunked table below can never outgrow max_pages_per_slot
            elif (self.chunked_prefill and r.prompt_len > self.prefill_chunk):
                with self.tracer.span("prefix_index", rid=r.rid):
                    off = self.mem.admit_chunked(r.slot, r.prompt)
                self._prefilling[r.slot] = (r, off)
            else:
                direct.append(r)
        groups: Dict[int, List[Request]] = {}
        for r in direct:
            groups.setdefault(self._bucket(r.prompt_len), []).append(r)
        for bucket, group in sorted(groups.items()):
            n = len(group)
            toks = np.zeros((n, bucket), np.int32)
            lens = np.zeros(n, np.int32)
            for i, r in enumerate(group):
                toks[i, : r.prompt_len] = r.prompt
                lens[i] = r.prompt_len
            trc = self.tracer
            if self.kv_layout == "paged":
                with trc.span("prefill.dispatch", bucket=bucket, n=n):
                    nxt, rows_k, rows_v = self._prefill_fn(bucket)(
                        self.params, jnp.asarray(toks), self._pack_meta(lens))
                bpp = bucket // self.page_size
                page_ids = np.zeros(n * bpp, np.int32)  # 0 -> null page
                real = 0
                with trc.span("prefix_index", n=n):
                    for i, r in enumerate(group):
                        # shared prefix pages keep id 0 in write_ids: their
                        # rows route to the null page (nothing written), the
                        # block table points at the existing physical pages
                        plan = self.mem.admit_slot(r.slot, r.prompt)
                        page_ids[i * bpp: i * bpp + len(plan.write_ids)] = \
                            plan.write_ids
                        real += len(plan.table) - plan.shared_pages
                with trc.span("prefill.insert", track="prefill"):
                    self.blocks = self._insert_fn(n, bucket)(
                        self.blocks, rows_k, rows_v,
                        self._pack_meta(page_ids))
                nbytes += real * self._page_bytes
            else:
                with trc.span("prefill.dispatch", bucket=bucket, n=n):
                    nxt, blocks_rows, k_pos_rows = self._prefill_fn(bucket)(
                        self.params, jnp.asarray(toks), self._pack_meta(lens))
                    self._insert([r.slot for r in group], blocks_rows,
                                 k_pos_rows)
                nbytes += self._pool_bytes  # at[].set rebuilds the pool
            if defer is not None:
                defer.append((nxt, list(enumerate(group))))
                continue
            # settle at prefill's OWN sync point (first token AND the
            # insert scatter), so prefill device time lands on the prefill
            # track instead of inside the next decode's device_wait
            with trc.span("prefill.device_wait", cat="device",
                          track="prefill"):
                jax.block_until_ready((nxt, self.blocks))
            nxt = np.asarray(nxt)
            now = self._now()
            for i, r in enumerate(group):
                self._start_decoding(r, int(nxt[i]), now)
        return nbytes

    def _advance_prefills(self, defer: Optional[List] = None
                          ) -> Tuple[int, int, int]:
        """Advance every mid-prefill request by ONE page-aligned chunk (so
        prefill work interleaves with decode instead of monopolizing the
        tick).  Slots sharing a (chunk, table-width) bucket are BATCHED
        into one forward, padded to a power-of-two batch bucket (rows with
        chunk_end 0 are inert: their writes route to the null page) so the
        per-group retrace count stays bounded like the admission path's.
        With `defer` (overlap prep window) the completing slots' settles
        are pushed as (handle, [(row, request), ...]) for
        `_settle_prefills` instead of blocking here.
        Returns (chunks processed, modeled KV bytes written, dispatches)."""
        nbytes = 0
        tok_bytes = self._page_bytes // self.page_size
        C = self.prefill_chunk
        plan: List[Tuple[int, Request, int, int]] = []
        for slot in sorted(self._prefilling):
            req, off = self._prefilling[slot]
            take = min(C, req.prompt_len - off)
            end = off + take
            self.pages.ensure(slot, end)
            nbytes += take * tok_bytes
            plan.append((slot, req, off, end))
        groups: Dict[int, List[Tuple[int, Request, int, int]]] = {}
        for item in plan:
            width = self._page_bucket(self.pages.n_pages_of(item[0]))
            groups.setdefault(width, []).append(item)
        n_chunks = 0
        n_dispatch = 0
        finished: List[int] = []
        for width, group in sorted(groups.items()):
            n = len(group)
            nb = self._n_bucket(n)
            toks = np.zeros((nb, C), np.int32)
            offs = np.zeros(nb, np.int32)
            ends = np.zeros(nb, np.int32)  # 0 marks an inert pad row
            tbl = np.full((nb, width), -1, np.int32)
            full = self.pages.table_array(self.capacity, width,
                                          only=[s for s, *_ in group])
            for i, (slot, req, off, end) in enumerate(group):
                toks[i, : end - off] = req.prompt[off:end]
                offs[i], ends[i] = off, end
                tbl[i] = full[slot]
            with self.tracer.span("prefill.chunk", width=width, n=n):
                nxt, self.blocks = self._chunk_fn(C, width, nb)(
                    self.params, self.blocks, jnp.asarray(toks),
                    self._pack_meta(offs, ends, tbl))
            n_chunks += n
            n_dispatch += 1
            nxt_np: Optional[np.ndarray] = None
            done_group: List[Tuple[int, Request]] = []
            for i, (slot, req, off, end) in enumerate(group):
                # index the pages this chunk just WROTE (never ahead of the
                # writes, so a sharer can only ever map written pages)
                self.mem.register_prefix(slot, req.prompt, upto=end)
                if end >= req.prompt_len:
                    finished.append(slot)
                    if defer is not None:
                        done_group.append((i, req))
                        continue
                    if nxt_np is None:
                        with self.tracer.span("prefill.device_wait",
                                              cat="device", track="prefill"):
                            jax.block_until_ready((nxt, self.blocks))
                        nxt_np = np.asarray(nxt)
                    self._start_decoding(req, int(nxt_np[i]), self._now())
                else:
                    self._prefilling[slot] = (req, end)
            if done_group:
                defer.append((nxt, done_group))
        for slot in finished:
            del self._prefilling[slot]
        return n_chunks, nbytes, n_dispatch

    # --- suspend / resume (cluster scale-to-zero) -------------------------
    def suspend(self) -> None:
        """Scale-to-zero: stop ticking; KV pool, queues, and in-flight
        request state stay intact (the slot-chunk analogue of parking a
        trainer's chunks — resume continues the exact token streams)."""
        if not self.suspended:
            self.suspended = True
            self.metrics.suspend_events.append((self._tick, "suspend"))

    def resume(self) -> None:
        if self.suspended:
            self.suspended = False
            self.metrics.suspend_events.append((self._tick, "resume"))

    # --- defrag -----------------------------------------------------------
    def defrag(self) -> bool:
        """Compact live pages to the low physical ids (one gather over the
        pool); block tables are rewritten, token streams are unchanged.
        Returns True if a move happened."""
        if self.mem is None:
            return False
        src = self.mem.defrag()  # also remaps the prefix index
        if src is None:
            return False
        idx = jnp.asarray(src)
        self.blocks = {k: jnp.take(v, idx, axis=1)
                       for k, v in self.blocks.items()}
        return True

    # --- main loop --------------------------------------------------------
    def _now(self) -> float:
        if self._clock is not None:
            return float(self._clock())
        if self._t0 is None:
            self._t0 = time.perf_counter()
        return time.perf_counter() - self._t0

    def submit(self, requests: Sequence[Request]) -> None:
        for r in sorted(requests, key=lambda r: r.arrival_time):
            # reject up front: a mid-run failure would abort in-flight
            # requests and leak the already-allocated slot
            if r.prompt_len + r.max_new_tokens > self.cache_len:
                raise ValueError(
                    f"request {r.rid}: prompt {r.prompt_len} + max_new "
                    f"{r.max_new_tokens} exceeds cache_len {self.cache_len}")
            ok, verdict = self.scheduler.try_submit(r)
            if not ok:
                # explicit backpressure: terminal REJECTED with a retry-
                # after hint, never queued, counted apart from sheds
                r.state = RequestState.REJECTED
                r.retry_after = verdict.retry_after
                r.t_finished = r.arrival_time
                self.tracer.instant("admission.reject", track="overload",
                                    rid=r.rid, tenant=r.tenant,
                                    reason=verdict.reason,
                                    retry_after=verdict.retry_after)
                self.tracer.count("serve.rejected")
            self.metrics.requests.append(r)

    def _paged_batch_inputs(self, active: List[int], n_new: np.ndarray
                            ) -> Tuple[np.ndarray, np.ndarray,
                                       np.ndarray, np.ndarray]:
        """Grow each active slot's block table to cover its span of
        `n_new[slot]` pending writes and build the width-bucketed
        (table, lengths, cow_src, cow_dst) dispatch inputs — shared by the
        plain decode (n_new == 1) and speculative verify (n_new == 1 +
        drafts) paths.  A slot whose first write lands in a SHARED page
        breaks the share here (fresh private page in the table) and carries
        the (old, new) pair so the dispatch copies the payload in-place;
        rows without a break copy the null page onto itself."""
        with self.tracer.span("cow_plan", n=len(active)):
            pos = self.scheduler.pool.pos
            cow_src = np.zeros(self.capacity, np.int32)
            cow_dst = np.zeros(self.capacity, np.int32)
            for slot in active:
                plan = self.mem.cow_plan(slot, int(pos[slot]))
                if plan is not None:
                    cow_src[slot], cow_dst[slot] = plan
                self.pages.ensure(slot, int(pos[slot]) + int(n_new[slot]))
            width = self._page_bucket(
                max(self.pages.n_pages_of(s) for s in active))
            # reuse the block table staged by last tick's overlap window iff
            # NOTHING moved since: the allocator's version counter bumps on
            # every table mutation (ensure/trim/cow/share/free), so a stale
            # plan — even one with identical page COUNTS but different ids —
            # can never be bound
            staged = self._plan
            self._plan = None
            if (staged is not None
                    and staged["version"] == self.pages.version
                    and staged["width"] == width
                    and staged["slots"] == active):
                table = staged["table"]
            else:
                table = self.pages.table_array(self.capacity, width,
                                               only=active)
            lengths = np.zeros(self.capacity, np.int32)
            for slot in active:
                lengths[slot] = pos[slot] + n_new[slot]
            return table, lengths, cow_src, cow_dst

    def _spec_launch(self, active: List[int], verify_fn
                     ) -> Callable[[], Tuple[int, float, int, int, int]]:
        """Launch one speculative solver phase and return its SETTLE
        closure: propose up to `spec_k` drafts per active slot, dispatch
        ONE (B, Q) verify over all k+1 positions (async — it is in flight
        when this returns), and defer the block + greedy-prefix emit +
        rejected-tail rollback to the closure.  The synchronous tick calls
        the closure immediately; the overlapped tick runs its prep window
        in between.  The closure returns (tokens emitted, step seconds,
        drafted, accepted, drafter device dispatches) and is bit-identical
        to sequential greedy either way."""
        k = self.spec_k
        Q = k + 1
        sched = self.scheduler
        pos_np = sched.pool.pos
        # drafting is part of the solver phase: the step timing that feeds
        # decode_s and the per-worker policy feedback starts HERE, so a
        # slow drafter (e.g. the draft model's own forwards) is visible
        t0 = time.perf_counter()
        with self.tracer.span("draft", n=len(active), k=k):
            contexts = []
            for slot in active:
                r = self._by_slot[slot]
                contexts.append(np.concatenate(
                    [np.asarray(r.prompt, np.int64),
                     np.asarray(r.generated, np.int64)]))
            proposals = self.drafter.propose(contexts, k)
            toks = np.zeros((self.capacity, Q), np.int32)
            n_new = np.zeros(self.capacity, np.int32)
            drafts: Dict[int, np.ndarray] = {}
            for i, slot in enumerate(active):
                r = self._by_slot[slot]
                # draft budget: never past the KV capacity or the request's
                # remaining token budget (wasted verification positions)
                budget = min(k, self.cache_len - 1 - int(pos_np[slot]),
                             r.max_new_tokens - r.n_generated - 1)
                d = np.asarray(proposals[i], np.int64)[: max(budget, 0)]
                drafts[slot] = d
                toks[slot, 0] = self.next_tok[slot, 0]
                if len(d):
                    toks[slot, 1: 1 + len(d)] = d
                n_new[slot] = 1 + len(d)

        with self.tracer.span("verify.dispatch", n=len(active)):
            if self.kv_layout == "paged":
                table, lengths, cow_src, cow_dst = self._paged_batch_inputs(
                    active, n_new)
                vtok, self.blocks = verify_fn(
                    self.params, self.blocks,
                    self._pack_meta(toks, np.asarray(pos_np, np.int32),
                                    table, lengths, cow_src, cow_dst), Q)
            else:
                vtok, self.blocks, self.k_pos = verify_fn(
                    self.params, self.blocks, self.k_pos,
                    self._pack_meta(toks, np.asarray(pos_np, np.int32),
                                    n_new))

        def settle() -> Tuple[int, float, int, int, int]:
            # blocking on the token output is blocking on the whole verify
            # computation (KV scatter included: same XLA executable) — and
            # unlike the blocks handle, vtok is never donated to a prefill
            # dispatched inside the overlap window
            with self.tracer.span("device_wait", cat="device",
                                  track="verify"):
                jax.block_until_ready(vtok)
            vtok_np = np.asarray(vtok)
            t_step = time.perf_counter() - t0
            sched.end_iteration()
            now = self._now()
            emitted = drafted = accepted = 0
            with self.tracer.span("rollback", n=len(active)):
                for slot in active:
                    req = self._by_slot[slot]
                    d = drafts[slot]
                    m = greedy_accept(d, vtok_np[slot])
                    drafted += len(d)
                    accepted += m
                    for j in range(m + 1):
                        tok = int(vtok_np[slot, j])
                        req.generated.append(tok)
                        self.next_tok[slot, 0] = tok
                        sched.pool.pos[slot] += 1
                        emitted += 1
                        if req.done():
                            break
                    if req.done():
                        del self._by_slot[slot]
                        self._release(req, now)
                    elif self.mem is not None:
                        # rollback: pages allocated solely for rejected
                        # drafts
                        self.mem.trim(slot, int(sched.pool.pos[slot]))
            return (emitted, t_step, drafted, accepted,
                    getattr(self.drafter, "dispatches_per_propose", 0))

        return settle

    def _decode_launch(self, active: List[int], decode_fn
                       ) -> Callable[[], Tuple[int, float, int, int, int]]:
        """Launch one plain greedy decode step and return its settle
        closure (the non-spec sibling of `_spec_launch`): COW/table
        planning + ONE packed metadata transfer + async dispatch here; the
        closure blocks, emits, and releases finished requests."""
        sched = self.scheduler
        pos_np = sched.pool.pos
        # t0 BEFORE the COW/table planning so decode_s keeps its
        # historical meaning (plan + dispatch + device completion)
        t0 = time.perf_counter()
        with self.tracer.span("decode.dispatch", n=len(active)):
            if self.kv_layout == "paged":
                table, lengths, cow_src, cow_dst = self._paged_batch_inputs(
                    active, np.ones(self.capacity, np.int32))
                nxt, self.blocks = decode_fn(
                    self.params, self.blocks,
                    self._pack_meta(self.next_tok,
                                    np.asarray(pos_np, np.int32),
                                    table, lengths, cow_src, cow_dst))
            else:
                nxt, self.blocks, self.k_pos = decode_fn(
                    self.params, self.blocks, self.k_pos,
                    self._pack_meta(self.next_tok,
                                    np.asarray(pos_np, np.int32)))

        def settle() -> Tuple[int, float, int, int, int]:
            with self.tracer.span("device_wait", cat="device",
                                  track="decode"):
                jax.block_until_ready(nxt)
            nxt_np = np.asarray(nxt)
            t_step = time.perf_counter() - t0
            sched.end_iteration()
            now = self._now()
            emitted = 0
            for slot in active:
                req = self._by_slot[slot]
                req.generated.append(int(nxt_np[slot]))
                self.next_tok[slot, 0] = int(nxt_np[slot])
                sched.pool.pos[slot] += 1
                emitted += 1
                if req.done():
                    del self._by_slot[slot]
                    self._release(req, now)
            return emitted, t_step, 0, 0, 0

        return settle

    def _finish_at_capacity(self) -> None:
        """A slot whose next write position is past the cache can't store
        another KV row: finish its request instead of silently overwriting
        the last row (pre-PR3 behavior clamped the position)."""
        sched = self.scheduler
        full = [s for s in self._by_slot if sched.pool.pos[s] >= self.cache_len]
        if full:
            now = self._now()
            for slot in full:
                self._release(self._by_slot.pop(slot), now)

    def _settle_prefills(self, pending: List) -> None:
        """Finish the prefill dispatches the overlap window deferred: ONE
        block covering every outstanding first-token handle plus the KV
        pool's latest handle, then the PREFILL -> DECODING transitions in
        dispatch order (same order the synchronous path runs them)."""
        if not pending:
            return
        with self.tracer.span("prefill.device_wait", cat="device",
                              track="prefill"):
            jax.block_until_ready(([h for h, _ in pending], self.blocks))
        now = self._now()
        for handle, group in pending:
            nxt_np = np.asarray(handle)
            for i, req in group:
                self._start_decoding(req, int(nxt_np[i]), now)

    def _prep_next_plan(self) -> None:
        """Stage next tick's decode block table inside the overlap window.
        The stage is only a HINT: `_paged_batch_inputs` binds it iff the
        allocator's version counter, the width bucket, and the active-slot
        list all still match at bind time — any admission, trim, COW break,
        or crash in between simply voids it (rebuild, never patch)."""
        self._plan = None
        if self.pages is None or not self.decode_enabled:
            return
        slots = sorted(self._by_slot)
        if not slots:
            return
        width = self._page_bucket(
            max(self.pages.n_pages_of(s) for s in slots))
        self._plan = {
            "version": self.pages.version,
            "width": width,
            "slots": slots,
            "table": self.pages.table_array(self.capacity, width,
                                            only=slots),
        }

    def _overlapped_phase(self, admitted: Sequence[Request], now: float
                          ) -> Tuple[int, int, int, int, float, int, int,
                                     int]:
        """The overlapped tick's middle: launch this tick's solver step
        FIRST (async), then do the host-side prep — fresh-admission
        prefills, chunked-prefill advancement, the disagg drain hook, and
        next tick's block-table plan — while the device computes.  Restores
        of parked/crash-retried slots bind BEFORE the launch so they join
        this tick's decode exactly like the synchronous path.  Emits the
        same streams as the synchronous tick: the reordering is
        timing-only (greedy decode conditions only on settled tokens, and
        every prep mutation the launch could observe happens at bind).
        Returns (admission_bytes, n_chunks, n_chunk_dispatch, emitted,
        t_step, drafted, accepted, draft_disp)."""
        trc = self.tracer
        sched = self.scheduler
        restores: List[Request] = []
        fresh: List[Request] = []
        for r in admitted:
            if self.mem is not None and self.mem.has_parked(r.rid):
                restores.append(r)
            else:
                fresh.append(r)
        admission_bytes = 0
        # late binding: parked restores (disagg handoffs, crash retries)
        # re-enter the decode batch THIS tick, so they go through before
        # the launch snapshot
        for r in restores:
            admission_bytes += self._restore_slot(r)
        self._finish_at_capacity()

        emitted = 0
        t_step = 0.0
        drafted = accepted = draft_disp = 0
        settle = None
        launch_t = 0.0
        active = sorted(self._by_slot) if self.decode_enabled else []
        if active:
            sched.begin_iteration()
            _, _, decode_fn, verify_fn = self._k_cache[self._k_mesh(self.k)]
            with trc.span("overlap.bind", track="overlap", n=len(active)):
                if self.drafter is not None:
                    settle = self._spec_launch(active, verify_fn)
                else:
                    settle = self._decode_launch(active, decode_fn)
            launch_t = trc.clock() if trc.enabled else 0.0

        pending: List = []
        with trc.span("overlap.prep", track="overlap", n_fresh=len(fresh)):
            if fresh:
                admission_bytes += self._do_prefill(fresh, defer=pending)
            n_chunks = n_chunk_dispatch = 0
            if self._prefilling:
                n_chunks, chunk_bytes, n_chunk_dispatch = \
                    self._advance_prefills(defer=pending)
                admission_bytes += chunk_bytes
            if self.overlap_hook is not None:
                # disagg: drain the OTHER pool's finished prefills into the
                # handoff queue while this pool's decode is in flight
                self.overlap_hook()
            self._prep_next_plan()

        if settle is not None:
            (emitted, t_step, drafted, accepted, draft_disp) = settle()
            if trc.enabled:
                # after-the-fact device envelope covering [dispatch, ready]
                # so attribution (and host_overlap_ratio) can see the prep
                # window's host spans as hidden behind device compute; it
                # lands on the solver's track (the `overlap` track is
                # excluded from the device-busy union by design)
                trc.complete("overlap.inflight", launch_t, trc.clock(),
                             cat="device",
                             track=("verify" if self.drafter is not None
                                    else "decode"), n=len(active))
        else:
            sched.sim_time += 1.0  # idle ticks still advance schedule time
        self._settle_prefills(pending)
        if settle is None and not pending and (fresh or n_chunks
                                               or restores):
            # prefill-only tick with nothing deferred (e.g. all chunked
            # admissions, or restore-only): settle the outstanding KV
            # scatters so wall-clock metrics charge the issuing tick
            with trc.span("prefill.device_wait", cat="device",
                          track="prefill"):
                jax.block_until_ready(self.blocks)
        return (admission_bytes, n_chunks, n_chunk_dispatch, emitted,
                t_step, drafted, accepted, draft_disp)

    def tick(self) -> TickRecord:
        if self.suspended:
            raise RuntimeError("ServeEngine is suspended; call resume() "
                               "before ticking")
        now = self._now()
        sched = self.scheduler
        kv0 = self._kv_prev
        trc = self.tracer
        tick_t0 = time.perf_counter() if trc.enabled else 0.0
        self._tick_meta = 0  # packed host->device transfers this tick

        # ---- fault phase: injected faults land BEFORE the scheduler so a
        # crash on the same tick as a scale event has a fixed, replayable
        # order (crash -> retry requeue -> deadline shed -> policies) ----
        if self.fault_injector is not None:
            for ev in self.fault_injector.poll(self._tick):
                self.apply_fault(ev)
        if self._retrying and not (self.breaker is not None
                                   and self.breaker.state == "open"):
            # an OPEN breaker holds crash victims in backoff too: re-
            # admitting them mid-storm just feeds the next crash (retry
            # amplification); they drain at half-open, when the probe
            # window is already watching for a re-fault
            self._requeue_retries()
        self._shed_expired(now)

        # ---- overload-control phase: the breaker watches the fault counts
        # accumulated since the last tick (injector + external crash_worker
        # calls land in _tick_faults either way); the ladder re-evaluates
        # its level from rolling attainment + queue pressure ----
        if self.breaker is not None:
            tr = self.breaker.update(
                self._tick,
                self._tick_faults["crashes"] + self._tick_faults["retries"])
            if tr is not None:
                self.metrics.breaker_events.append((self._tick, tr))
                trc.instant(f"breaker.{tr}", track="overload",
                            tick=self._tick)
                trc.count("serve.breaker_transitions")
        if self.ladder is not None:
            prev = self.ladder.level
            att = self.slo.attainment() if self.slo is not None else None
            lvl = self.ladder.update(att, sched.n_arrived(now),
                                     self.capacity)
            if lvl != prev:
                name = DegradationLadder.LEVELS[lvl]
                self.metrics.brownout_events.append((self._tick, lvl, name))
                trc.instant("degrade.enter" if lvl > prev
                            else "degrade.exit", track="overload",
                            level=lvl, label=name)
                trc.count("serve.degrade_transitions")
                trc.gauge("serve.brownout_level", lvl)
                self._apply_degradation(lvl)
            self._brownout_actions(now)

        # ---- scheduler phase: policies may rescale/rebalance the pool ----
        with trc.span("schedule", k=sched.n_workers):
            stats: Dict = dict(self._last_stats)
            k_before = sched.n_workers
            # only policies can rescale inside between_ticks; skip the
            # per-slot worker snapshot on the hot path when none installed
            live, before = (self._slot_workers() if sched.policies
                            else ([], {}))
            sched.between_ticks(stats)
            if sched.n_workers != k_before:
                self.metrics.scale_events.append(
                    (self._tick, k_before, sched.n_workers))
                # policies resized the assignment in between_ticks, so
                # resize() below only re-meshes; record the slot moves here
                self._record_resize_moves(sched.n_workers, live, before)
                self.resize(sched.n_workers)
        # priority admission: a full pool no longer blocks a high-priority
        # request — a strictly lower-priority in-flight decode is parked
        # (pages to host), not just queued behind
        with trc.span("admit"):
            limit = allow = None
            if self.breaker is not None:
                lim = self.breaker.admit_limit()
                if lim == 0:
                    # open: recovery traffic only — crash victims re-admit
                    # so recovery drains, fresh load waits the storm out
                    allow = lambda r: r.retries > 0  # noqa: E731
                elif lim is not None:
                    limit = lim  # half-open probe budget
            admitted = sched.admit(
                now, preempt=self._preempt_for if (self.mem is not None
                                                   and self.evict) else None,
                limit=limit, allow=allow)
        if self.overlap:
            # ---- overlapped middle: launch the solver step first, prep
            # next tick's work while the device computes ----
            (admission_bytes, n_chunks, n_chunk_dispatch, emitted, t_step,
             drafted, accepted, draft_disp) = self._overlapped_phase(
                admitted, now)
        else:
            admission_bytes = self._do_prefill(admitted) if admitted else 0
            n_chunks = 0
            n_chunk_dispatch = 0
            if self._prefilling:
                n_chunks, chunk_bytes, n_chunk_dispatch = \
                    self._advance_prefills()
                admission_bytes += chunk_bytes
            self._finish_at_capacity()

            # ---- solver phase: one pool-wide decode (or spec-verify)
            # step ----
            emitted = 0
            t_step = 0.0
            drafted = accepted = draft_disp = 0
            # a prefill-only pool half never decodes: prefilled slots wait
            # in _by_slot for the disagg handoff (the else-branch below
            # still advances schedule time and settles the prefill
            # scatters)
            active = sorted(self._by_slot) if self.decode_enabled else []
            if active:
                sched.begin_iteration()
                _, _, decode_fn, verify_fn = \
                    self._k_cache[self._k_mesh(self.k)]
                if self.drafter is not None:
                    settle = self._spec_launch(active, verify_fn)
                else:
                    settle = self._decode_launch(active, decode_fn)
                # synchronous path: settle immediately — the launch/settle
                # split only reorders work when overlap=True
                (emitted, t_step, drafted, accepted, draft_disp) = settle()
            else:
                sched.sim_time += 1.0  # idle ticks still advance time
                if admitted or n_chunks:
                    # prefill-only tick: settle the outstanding KV
                    # scatters so wall-clock metrics charge the work to
                    # the tick that issued it
                    with trc.span("prefill.device_wait", cat="device",
                                  track="prefill"):
                        jax.block_until_ready(self.blocks)

        if self.debug_checks:
            # page-leak guard: every live slot must hold EXACTLY the pages
            # its live tokens need, every refcount must equal the page's
            # true reader count, and the prefix index must point only at
            # live pages — a page kept for a rejected draft, leaked by an
            # at-capacity finish, or a refcount drifting through a
            # share/COW/park cycle fails the tick it happens
            sched.pool.check_invariants()
            if self.mem is not None:
                live = {s: int(sched.pool.pos[s]) for s in self._by_slot}
                live.update({s: off for s, (_, off)
                             in self._prefilling.items()})
                self.mem.check(live)

        # modeled per-worker timing attribution feeds the same policy
        # feedback loop as training (load-proportional split of the step)
        loads = sched.active_per_worker()
        total = max(int(loads.sum()), 1)
        # injected stragglers inflate their worker's modeled share so the
        # mitigation policy sees them exactly like an organic slow worker
        slow = self._slow_factors
        self._last_stats = {
            "task_times": {w: t_step * loads[w] / total * slow.get(w, 1.0)
                           for w in range(sched.n_workers)},
            "per_sample_times": {w: t_step / total * slow.get(w, 1.0)
                                 for w in range(sched.n_workers)},
        }
        self._settle_recoveries()

        self._stamp_cache_sizes()
        kv = {}
        if kv0 is not None:
            kv1 = self.mem.stats()
            self.metrics.kv_stats = kv1
            self._kv_prev = kv1
            delta = lambda k: kv1[k] - kv0[k]  # noqa: E731
            kv = dict(
                shared_page_hits=delta("shared_page_hits"),
                cow_breaks=delta("cow_breaks"),
                parked=delta("parked_total"),
                restored=delta("restored_total"),
                kv_moved_bytes=(delta("park_bytes")
                                + delta("restore_bytes")),
                shared_extra_pages=kv1["shared_extra"],
            )
        rec = TickRecord(tick=self._tick, now=self._now(),
                         n_active=len(self._by_slot),
                         n_workers=sched.n_workers,
                         occupancy=sched.pool.occupancy(),
                         decode_s=t_step, admitted=len(admitted),
                         tokens_emitted=emitted,
                         admission_bytes=admission_bytes,
                         prefill_chunks=n_chunks,
                         prefill_dispatches=n_chunk_dispatch,
                         page_occupancy=(self.pages.occupancy()
                                         if self.pages else 0.0),
                         spec_drafted=drafted, spec_accepted=accepted,
                         draft_dispatches=draft_disp,
                         crashes=self._tick_faults["crashes"],
                         retries=self._tick_faults["retries"],
                         shed=self._tick_faults["shed"],
                         brownout_level=(self.ladder.level
                                         if self.ladder is not None else 0),
                         meta_transfers=self._tick_meta,
                         **kv)
        self._tick_faults = {"crashes": 0, "retries": 0, "shed": 0}
        self.metrics.ticks.append(rec)
        if trc.enabled:
            trc.count("serve.ticks")
            trc.count("serve.tokens_emitted", emitted)
            trc.observe("serve.tick_s", time.perf_counter() - tick_t0)
            if t_step > 0.0:
                trc.observe("serve.decode_s", t_step)
        self._tick += 1
        return rec

    def run(self, requests: Sequence[Request], *,
            max_ticks: int = 100_000) -> ServeMetrics:
        """Drive the open-loop workload to completion."""
        if self._clock is not None:
            raise ValueError("run() paces on the wall clock; with an "
                             "injected clock drive tick() externally "
                             "(see repro.cluster.jobs.ServeJob)")
        self.submit(requests)
        self._now()  # start the clock
        sched = self.scheduler
        while ((sched.has_pending or self._by_slot or self._prefilling
                or self._retrying)
               and self._tick < max_ticks):
            if not self._by_slot and not self._prefilling and sched.has_pending:
                wait = sched.next_arrival() - self._now()
                if wait > 0:  # idle until the next open-loop arrival
                    time.sleep(min(wait, 0.05))
            with set_mesh(self.mesh):  # re-entered so resize(k) takes effect
                self.tick()
        self.metrics.wall_s = self._now()
        return self.metrics
