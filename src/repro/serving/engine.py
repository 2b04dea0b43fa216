"""TIDE Inference Serving Engine — continuous batching over a fused
on-device decode superstep, under a pluggable policy control plane.

Control plane (``serving/policy.py``): every host-side scheduling
decision the engine makes between superstep dispatches is delegated to
one composed ``ServingPolicy`` —

  * **admission** (which pending request enters a freed lane):
    ``FifoAdmission`` (default, byte-parity with the pre-policy
    engine), ``PriorityAdmission``, or ``DeadlineAdmission`` (EDF over
    ``Request.deadline`` — latency-SLO serving);
  * **commit** (how chunked-refill pipelines land): ``CohortCommit``
    (default — an admission batch's pipelines activate together,
    densest decode rounds) or ``EagerCommit`` (each pipeline lands the
    moment its prefill completes — short-prompt TTFT under mixed
    bursts);
  * **speculation**: the Eq. 5 adaptive gate evaluated in-graph from a
    threshold table, plus a runtime park/resume control that can turn
    speculation *and* signal capture off when acceptance-adjusted gain
    stays below break-even, probing periodically with a
    forced-speculation superstep to detect recovery.

Policy hooks run at admission, refill-group formation, commit, and
superstep dispatch — host-side decisions only, so the
one-sync-per-superstep pipelining below is untouched; the speculation
tables share one shape/dtype, so park/probe swaps never retrace.
Engine knobs travel in one ``ServingConfig``
(``ServingEngine(config=..., policy=...)``); the legacy control kwargs
(``gate_arrivals``, ``completion_sink``, bare ``prefill_chunk``)
survive as deprecation shims that fold into it byte-identically.

Architecture (slot lifecycle):

  * The device holds B resident batch lanes ("slots"): target KV/SSM
    cache, EAGLE draft cache, and the superstep carry/state.  Decode
    runs as a jitted **superstep** — ``lax.scan`` over K speculative
    rounds in one compiled function (``core.speculative.decode_superstep``)
    with the Eq. 5 speculate-vs-plain choice, token commit/EOS/budget
    masks, acceptance-EMA, and per-round ``extract_pack`` signal
    compaction all in-graph.  One device→host sync per K rounds.
  * A host-side ``serving.scheduler.Scheduler`` owns slot admission
    (order per the ``AdmissionPolicy``): ``serve_stream(request_iter)``
    keeps the engine resident across an entire request stream, and
    between supersteps **refills** finished slots from the pending
    queue — no wave teardown, no convoy effect from one long request
    holding B-1 idle lanes.
  * A refill is a jitted per-slot op: the new prompt is prefilled and
    its cache lanes are written into the *live* device state
    (``speculative.scatter_target_cache`` / ``eagle.scatter_draft_rows``
    — gather+where with fixed shapes), and that slot's superstep carry
    (position, budget, EOS flag, acceptance bookkeeping) is reset
    in-graph (``speculative.refill_superstep_state``).  Refill batches
    over all slots freed in the same gap.
  * **Chunked refill prefill** (``prefill_chunk=C`` > 0, multiple of 8):
    a one-shot refill stalls every resident decode lane for the full
    prompt width — the long-tail-prompt convoy effect.  Chunked, each
    refill group becomes a **chunk pipeline**: the prompt is prefilled
    into a *staging* cache pair in fixed-width chunks (a ragged first
    chunk <= C, then exactly C), so the longest uninterruptible prefill
    op is C wide no matter the prompt.  Each inter-superstep gap runs a
    chunk plan under a prefill budget of ``batch x C`` row-tokens (pad
    rows counted): every live pipeline advances one chunk, then the
    budget left over goes chunk by chunk to the pipeline with the most
    prompt columns left, so a lone long prompt prefills in one gap and
    a cohort's members finish together.  The budget is the gap a full
    batch admitted at once takes at one chunk per pipeline, so the
    worst gap resident lanes see does not grow; it only spends what
    goes unused when few rows prefill.  The first chunk is a plain
    prefill; continuations extend
    the staging caches through the decode path and feed the draft
    seeding the same chunk's (capture, next-token) pairs
    (``eagle.seed_chunk_pairs``) — bitwise-identical to the one-shot
    prefill on the valid cache region and emitted logits
    (tests/test_chunked_prefill.py).  The final chunk is dispatched
    fused with its commit: sample the first token, scatter the staging
    lanes into the live state and reset the lanes' carry — the same op
    shape as a one-shot refill, with first tokens riding the next
    telemetry pull.  Admission is chunk-aware
    (``Scheduler.refill_groups``): co-admitted prompts split into
    per-width pipelines whose chunks interleave through the same gaps,
    so a short prompt neither pays a long prompt's padding nor rides
    its multi-chunk pipeline — and under the default ``CohortCommit``
    the pipelines of one admission batch form a *cohort* that commits
    together (when its slowest member finishes), so the lanes of one
    admission activate in the same gap and decode rounds stay as dense
    as a one-shot refill's (``EagerCommit`` trades that density for
    short-prompt TTFT); with no
    resident lane decoding (stream prologue, drained-empty supersteps)
    chunk plans run back-to-back to the next commit instead of
    trickling one per empty gap.  Mid-prefill lanes stay inert
    for decode masks and the reseed ring until their commit; stats count
    them separately (``prefill_lane_rounds`` — excluded from the
    occupancy denominator) and the TTFT clock starts at *admission*
    (``Request.admit_t``), so chunked prefill is charged for every
    chunk.  The stream prologue is just the pipeline path too: with
    chunking on, lanes start inert and the initial batch flows through
    the same pipelines (``serve_wave`` callers inherit chunking
    unchanged).  Deterministic stall metrics:
    ``stats.prefill_op_width.max`` (longest uninterruptible prefill op)
    and ``stats.prefill_gap_tokens`` / ``prefill_row_tokens``
    (per-plan / total prefill row-tokens), gated in
    ``benchmarks/bench_continuous.py`` next to the wall-clock goodput.
  * **Paged KV serving** (``page_size=P`` > 0): every target/draft KV
    leaf becomes a pool of fixed ``P``-token pages behind one
    host-authoritative per-lane block table (``core.paging``); the
    pool's extra trash page absorbs every write dense decoding would
    silently drop (inert lanes, positions past ``max_len``), so
    inactive lanes can never clobber mapped pages.  Lanes reserve
    ``ceil((width + budget + gamma + 1) / P)`` pages at admission, and
    the scheduler's admission guard defers a request the pool cannot
    cover (``stats.admission_deferrals``) — slot count is bounded by
    HBM actually used, not ``batch x max_len``.  The allocator is
    host-side numpy bookkeeping; the engine ships immutable table
    snapshots to the device only in gaps where the table changed, a
    host→device upload that adds **zero** syncs.  Decode scatters
    through the table and attends through the gathered dense per-lane
    view (on TPU, through the block-table Pallas kernels
    ``flash_attn_paged``/``verify_attn_paged``) — the identical
    dispatch over identical bytes — so paged serving is **bitwise**
    equal to dense serving on full streams (tests/test_paged.py).
    Committed prompt-prefix pages are published to a refcounted COW
    registry keyed by *provenance* (refill rows/width/pad, the token
    prefix, the draft deploy seq — keys match only where page bytes
    are guaranteed identical); an admission whose rows all hit adopts
    the donor's physical pages at commit (no device compare) and its
    chunk pipeline resumes past the covered chunks, cutting
    shared-system-prompt prefill work (``benchmarks/bench_paged.py``).
    Divergent writes into shared pages fork first (``fork_for_write``)
    — the serving engine never needs to by construction, since shared
    pages cover only positions below every borrower's first divergent
    write.  ``reseed_window`` is mutually exclusive with paging (the
    deploy-time re-seed op rewrites dense draft lanes).
  * **Tree speculation** (``tree_width=W`` >= 1): each speculative
    round drafts a token *tree* instead of a linear chain — W top-k
    first continuations, each extended to a gamma-deep branch by the
    EAGLE draft — flattened branch-major into one fixed block of
    T = W*gamma + 1 rows (slot 0 = the committed token, branch r's
    depth-j node at slot 1 + r*gamma + (j-1)).  One tree-masked target
    forward (``verify_attn(tree=(W, gamma))``; in-block visibility is
    same-branch ancestors plus the shared root, derived from iota
    arithmetic — no mask tensors) scores every branch at the cost of a
    single verify pass, the acceptance rule (greedy match or
    SpecInfer-style sequential residual sampling over the sibling set)
    picks the longest accepted root path, and the commit *compacts*
    that branch's K/V rows into the chain layout before
    ``commit_cache`` — non-path rows stay past the committed length
    where the next block's scatter rewrites them (dense) or routes to
    the trash page (paged), so allocator invariants are untouched.
    Only accepted-path features enter signal capture, so SignalStore
    semantics are unchanged.  ``tree_width=1`` is the degenerate tree,
    **bitwise identical** to the chain engine on full streams
    (tests/test_tree.py); 0 (default) keeps the chain path compiled
    as-is.  Attention mixers only (``T.tree_check``).
  * Pipelining is preserved: superstep t+1 is dispatched *before*
    superstep t's telemetry is pulled to the host; completions observed
    in t schedule refills that are enqueued behind t+1 and take effect
    in t+2.  The refilled requests' first tokens ride along with the
    next telemetry pull, so refill adds **zero** extra host syncs.
    ``ServingStats``/timeline and the Algorithm 1 controller decisions
    are reconstructed host-side from per-round device telemetry
    (``TrainingController.observe_gated`` keeps the measurement sequence
    identical to the per-step loop).

Decoupled draft training hooks:

  * ``deploy_source`` — a callable polled once per superstep (a host
    attribute read, zero device syncs) returning the training service's
    latest published ``DraftVersion``; a new version hot-swaps
    ``dparams`` for the next dispatch.
  * ``reseed_window=W`` — the superstep state additionally maintains a
    per-lane rolling ring of the last W (feature, token) pairs the
    draft cache ingested; on deploy, one enqueued device op
    (``eagle.reseed_draft_rows_from_ring``) rebuilds resident lanes'
    trailing draft K/V under the new draft, so its acceptance gain
    applies immediately instead of at lane retirement.
  * ``gate_arrivals`` — the scheduler holds requests until their trace
    arrival time; with all slots idle the engine emits *idle
    supersteps* (no dispatch, a bounded sleep) — the slack the
    single-device background trainer consumes.
  * ``completion_sink`` + ring-buffered ``ServingStats`` (P² percentile
    sketches past the retention window) bound host memory on endless
    streams.

PRNG: sampling uses per-request streams — lane keys are
``fold_in(fold_in(base_seed, sid), step)`` with ``sid`` the request's
admission ordinal and ``step`` its private decode-step counter, so
*sampled* decoding is scheduling-invariant too: stream, wave, stepwise,
and any refill timing emit byte-identical per-request tokens
(tests/test_continuous.py::test_sampled_stream_scheduling_invariant).
The old batch-global key chain made sampled parity hold only on
refill-free streams.

Observability (``repro/obs``; docs/observability.md): the engine takes
three optional host-side collaborators — a span ``tracer`` (spans that
tile each superstep iteration: dispatch, the host's waits on telemetry,
unpack, boundary, refill and prefill chunks, idle; plus token-delivery,
sched, deploy and spec instants), a per-request flight ``recorder``
(admit → prefill chunks → first token → per-round commits → finish),
and a ``metrics`` registry that ``ServingStats`` registers its
counters/histograms/derived gauges into under the ``serving.*``
namespace (``spec.*`` and ``paging.*`` gauges ride along).  Every hook
sits at a boundary the host already crosses — nothing new is pulled
from the device, so observability-on serving adds **zero** syncs; the
defaults (``DEFAULT_TRACER``, which records only while a JAX profiler
session runs, and ``NULL_RECORDER``) make the disabled path a single
check; obs-on streams are byte-identical to obs-off
(tests/test_obs.py, gated with a ≤1.03x wall bound in
benchmarks/bench_hotloop.py).

``serve_wave`` is a thin compatibility wrapper over ``serve_stream``
(a stream containing exactly one wave); waves smaller than the engine
batch are padded with inert zero-budget slots.  ``superstep_rounds=0``
selects the legacy per-step host loop, kept as the parity reference —
every scheduling policy emits byte-identical per-request token streams
(tests/test_continuous.py, tests/test_superstep.py).

All device steps are jitted with fixed shapes; per-request raggedness is
handled with masks (pads, finished requests), and refill prompt lengths
are bucketed to multiples of 8 to bound recompilation.  The live
cache/draft-cache/superstep-state buffers are donated back to each
dispatch (``donate_argnums``), so steady-state decode re-uses the same
device allocations instead of re-allocating telemetry buffers per call.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Callable, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import eagle, paging, speculative as spec
from repro.core.adaptive import AdaptiveDrafter
from repro.core.controller import Decision, TrainingController
from repro.core.signals import SignalExtractor
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.obs.metrics import MetricsRegistry
from repro.obs.recorder import NULL_RECORDER
from repro.obs.trace import DEFAULT_TRACER
from repro.serving.policy import ServingConfig, ServingPolicy
from repro.serving.request import Request, inert_request
from repro.serving.scheduler import Scheduler
from repro.serving.stats import P2Quantile, Ring


def _deprecated_kwarg(name: str, replacement: str):
    warnings.warn(
        f"ServingEngine({name}=...) is deprecated; pass {replacement} "
        "instead (see serving.policy.ServingConfig / ServingPolicy)",
        DeprecationWarning, stacklevel=3)

# sampling-stream id for lanes that never emit (inert padding, free
# slots) — any fixed value works, it is only ever folded into keys whose
# samples are discarded; kept positive (fold_in rejects negatives)
INERT_SID = 0x7FFFFFFF


# ``serving.*`` registry counters exposed as plain ServingStats
# attributes (int unless noted float below)
_STATS_COUNTERS = (
    "tokens_out", "steps", "spec_steps", "dispatches", "refills",
    "idle_supersteps", "deploys", "reseeds", "completed",
    "accept_len_n", "lane_rounds", "busy_lane_rounds",
    "prefill_chunks", "prefill_lane_rounds", "prefill_row_tokens",
    "pages_peak", "prefix_hits", "prefix_tokens_saved",
    "admission_deferrals", "preemptions", "restores", "shed_requests",
)
_STATS_FLOAT_COUNTERS = ("wall_s", "accept_len_sum")


class _CounterView:
    """Descriptor exposing the registry counter ``serving.<name>`` as a
    plain read/write attribute, so engine idioms like
    ``stats.tokens_out += n`` keep working unchanged while the value
    lives in the shared :class:`repro.obs.metrics.MetricsRegistry`."""
    __slots__ = ("name",)

    def __set_name__(self, owner, name):
        self.name = name

    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        return obj._counters[self.name].value

    def __set__(self, obj, value):
        obj._counters[self.name].value = value


class ServingStats:
    """Engine counters, backed by the ``serving.*`` namespace of a
    :class:`repro.obs.metrics.MetricsRegistry`.  ``tokens_out`` counts
    exactly the tokens that survive in ``Request.generated`` after
    ``Request.finish()``'s budget truncation — the first sampled token
    included — so it always equals the sum of emitted stream lengths.

    Every counter attribute below is a thin view over a registry
    ``Counter`` (``stats.tokens_out`` IS ``serving.tokens_out``), the
    prefill-stall Peaks and latency sketches are registry
    ``Histogram``s, and the derived properties (throughput, occupancy,
    percentiles) are registered as callback gauges — one
    ``registry.snapshot()`` exposes everything this object exposes.
    Constructing a ServingStats against a shared registry zeroes the
    ``serving.*`` namespace (stats reset == counter reset); with no
    registry given it owns a private one.

    Host retention is bounded for endless streams: ``ttfts`` /
    ``latencies`` / ``timeline`` are drop-oldest rings of the trailing
    ``retain`` entries, while the percentile properties stay whole-stream
    accurate through P² sketches (exact until the rings overflow)."""

    # counter semantics (see also docs/observability.md):
    #   dispatches       decode-step/superstep launches (sync points)
    #   refills          slots refilled in-flight (async, no sync)
    #   idle_supersteps  gated-arrival gaps with nothing to dispatch
    #   deploys          draft hot-swaps picked up from the deploy slot
    #   reseeds          deploy-time draft-cache re-seed dispatches
    #   lane_rounds      batch lanes x executed rounds
    #   busy_lane_rounds lanes that committed >=1 token that round
    #   prefill_chunks   chunk-pipeline dispatches
    #   prefill_lane_rounds  lane-rounds spent mid-prefill (inert)
    #   prefill_row_tokens   Σ rows × width over all prefill ops
    #   pages_peak       peak pages mapped at once (paged engines)
    #   prefix_hits      prefix-page adoption events (COW)
    #   prefix_tokens_saved  prompt tokens served from shared pages
    #   admission_deferrals  admit candidates vetoed on page pressure
    #   preemptions      resident lanes spilled to host for a tighter
    #                    arrival (Request.evictions sums to this)
    #   restores         spilled requests re-admitted onto a lane
    #   shed_requests    queued requests dropped by the shed policy
    #                    (finish with Request.shed=True, empty stream)

    def __init__(self, retain: int = 4096, registry=None):
        from repro.obs.metrics import MetricsRegistry
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.retain = retain
        self._counters = {}
        for name in _STATS_COUNTERS:
            c = self.registry.counter(f"serving.{name}")
            c.value = 0
            self._counters[name] = c
        for name in _STATS_FLOAT_COUNTERS:
            c = self.registry.counter(f"serving.{name}")
            c.value = 0.0
            self._counters[name] = c
        self.ttfts = Ring(retain)
        self.latencies = Ring(retain)
        self.timeline = Ring(retain)
        # prefill-stall distributions + latency sketches: registry
        # histograms (Peak + P² underneath), recreated on reset
        self.prefill_op_width = self.registry.histogram(
            "serving.prefill_op_width", (0.5,), reset=True)
        self.prefill_gap_tokens = self.registry.histogram(
            "serving.prefill_gap_tokens", (0.5,), reset=True)
        self._ttft_hist = self.registry.histogram(
            "serving.ttft_s", (0.5,), reset=True)
        self._lat_hist = self.registry.histogram(
            "serving.latency_s", (0.5, 0.95), reset=True)
        for gname, prop in (
                ("serving.throughput_tok_s", "throughput"),
                ("serving.occupancy", "occupancy"),
                ("serving.accept_len", "accept_len"),
                ("serving.ttft_p50_s", "ttft_p50"),
                ("serving.latency_p50_s", "latency_p50"),
                ("serving.latency_p95_s", "latency_p95")):
            self.registry.gauge(
                gname, fn=functools.partial(getattr, self, prop))

    def record_ttft(self, x: float):
        self.ttfts.append(x)
        self._ttft_hist.add(x)

    def record_latency(self, x: float):
        self.latencies.append(x)
        self._lat_hist.add(x)

    @property
    def accept_len(self) -> float:
        return self.accept_len_sum / max(self.accept_len_n, 1)

    @property
    def throughput(self) -> float:
        return self.tokens_out / max(self.wall_s, 1e-9)

    @property
    def occupancy(self) -> float:
        """Fraction of *decode-eligible* lane-rounds that committed
        tokens — the slot utilization continuous batching exists to
        maximize.  Lanes still chunk-prefilling their prompt are counted
        separately (``prefill_lane_rounds``) and excluded from the
        denominator: a mid-prefill lane is busy with admission work, not
        idle capacity."""
        return self.busy_lane_rounds / max(
            self.lane_rounds - self.prefill_lane_rounds, 1)

    def _pct(self, xs, sketch: P2Quantile, q: float) -> float:
        if sketch.n_obs > len(xs):      # ring overflowed → whole-stream
            return sketch.value         # P² estimate
        return float(np.percentile(np.asarray(xs), q)) if xs else 0.0

    @property
    def ttft_p50(self) -> float:
        return self._pct(self.ttfts, self._ttft_hist.sketches[0.5], 50)

    @property
    def latency_p50(self) -> float:
        return self._pct(self.latencies, self._lat_hist.sketches[0.5], 50)

    @property
    def latency_p95(self) -> float:
        return self._pct(self.latencies, self._lat_hist.sketches[0.95], 95)


for _name in _STATS_COUNTERS + _STATS_FLOAT_COUNTERS:
    _view = _CounterView()
    _view.__set_name__(ServingStats, _name)
    setattr(ServingStats, _name, _view)
del _name, _view

# Back-compat alias (pre-continuous-batching name).
EngineStats = ServingStats


class _ChunkPipeline:
    """Host bookkeeping for one in-flight chunked refill group.

    Holds the (slot, request) assignments, the padded prompt / lane-map
    arrays (exactly as ``_refill_arrays`` builds them for a one-shot
    refill), and the staging target/draft caches the chunk ops thread.
    The prompt is processed left to right: the first op is ragged
    (``width - (n_chunks-1)*chunk``, a multiple of 8 in [8, chunk], so
    the final chunk always ends exactly at ``width``) and every
    continuation is exactly ``chunk`` wide — fixed compiled shapes, one
    trace per refill-row bucket."""

    def __init__(self, admitted, args, chunk: int, cohort: int = 0,
                 order: int = 0):
        (self.toks, self.pad, self.mask, self.src, self.budgets,
         self.sids) = args
        self.admitted = admitted
        self.rows = int(self.toks.shape[0])
        self.width = int(self.toks.shape[1])
        n_chunks = -(-self.width // chunk)
        self.first_width = self.width - (n_chunks - 1) * chunk
        self.chunk = chunk
        self.pos = 0            # prompt prefix already prefilled
        # pipelines spawned from one admission batch form a *cohort*:
        # their chunks pipeline independently, but they commit together
        # when the slowest member finishes, so the lanes of one
        # admission activate in the same gap (exactly as a one-shot
        # refill op activates them) and decode rounds stay dense instead
        # of fragmenting across staggered activations
        self.cohort = cohort
        self.order = order
        self.ready = False      # fully prefilled, waiting on the cohort
        self.cache = None       # staging target cache (rows x width)
        self.dcache = None      # staging draft cache
        self.logits = None      # last-position logits after latest chunk
        self.caps_last = None   # last capture column after latest chunk
        # ---- paged prefix sharing (engine fills these at spawn)
        self.resume_q = 0       # >0: skip prefilling [0, q) — the rows
        #                         adopted shared prefix pages covering it
        self.resume_rows = None  # (rows, ceil(q/P)) adopted page ids
        self.pub_entries = []   # (slot, provenance key, n_pages) to
        #                         publish when this pipeline commits
        self.deploy_seq = 0     # draft version at spawn (a mid-pipeline
        #                         deploy makes draft pages unshareable)

    @property
    def done(self) -> bool:
        return self.pos >= self.width

    def width_at(self, pos: int) -> int:
        """Width of the chunk that starts at prompt column ``pos``."""
        return self.first_width if pos == 0 else self.chunk


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, dcfg: ModelConfig,
                 dparams, *, gamma: int = 3, max_len: int = 160,
                 batch_size: int = 4, greedy: bool = True,
                 drafter: Optional[AdaptiveDrafter] = None,
                 controller: Optional[TrainingController] = None,
                 extractor: Optional[SignalExtractor] = None,
                 ema: float = 0.9, seed: int = 0,
                 superstep_rounds: int = 8,
                 eos_id: Optional[int] = None,
                 deploy_source: Optional[Callable[[], object]] = None,
                 reseed_window: int = 0,
                 gate_arrivals: Optional[bool] = None,
                 completion_sink: Optional[Callable[[Request], None]]
                 = None,
                 idle_wait_s: float = 0.005,
                 prefill_chunk: Optional[int] = None,
                 config: Optional[ServingConfig] = None,
                 policy: Optional[ServingPolicy] = None,
                 tracer=None, recorder=None, metrics=None):
        # ------------------------------------------------ configuration
        # One ServingConfig is the source of truth for every serving
        # knob.  Callers either pass ``config=`` (the unified API; the
        # individual knob kwargs are then ignored) or the individual
        # kwargs (assembled into a config here).  The pre-policy control
        # kwargs survive as thin deprecation shims that fold into the
        # config — byte-identical behavior, plus a DeprecationWarning.
        knobs = dict(gamma=(gamma, 3), batch_size=(batch_size, 4),
                     max_len=(max_len, 160), greedy=(greedy, True),
                     superstep_rounds=(superstep_rounds, 8),
                     eos_id=(eos_id, None), ema=(ema, 0.9),
                     seed=(seed, 0), reseed_window=(reseed_window, 0),
                     idle_wait_s=(idle_wait_s, 0.005))
        if config is None:
            config = ServingConfig(
                **{k: v for k, (v, _) in knobs.items()})
        else:
            # config is the source of truth; a knob kwarg passed
            # alongside it would be silently ignored — fail loudly
            clash = [k for k, (v, d) in knobs.items() if v != d]
            if clash:
                raise ValueError(
                    f"ServingEngine got both config= and knob kwargs "
                    f"{clash}; set them on the ServingConfig instead")
            config = dataclasses.replace(config)   # engine-private copy
        if gate_arrivals is not None:
            _deprecated_kwarg("gate_arrivals",
                              "ServingConfig(gate_arrivals=...)")
            config.gate_arrivals = gate_arrivals
        if completion_sink is not None:
            _deprecated_kwarg("completion_sink",
                              "ServingConfig(completion_sink=...)")
            config.completion_sink = completion_sink
        if prefill_chunk is not None:
            if prefill_chunk:
                _deprecated_kwarg("prefill_chunk",
                                  "ServingConfig(prefill_chunk=...)")
            config.prefill_chunk = prefill_chunk
        self.config = config
        self.cfg, self.dcfg = cfg, dcfg
        self.params, self.dparams = params, dparams
        self.gamma, self.max_len = config.gamma, config.max_len
        self.batch = config.batch_size
        self.greedy = config.greedy
        self.controller = controller
        self.extractor = extractor
        self.accept_ema = 1.0
        self._ema = config.ema
        self.superstep_rounds = config.superstep_rounds
        self.eos_id = config.eos_id
        # ------------------------------------------------ control plane
        # Every host-side scheduling decision (admission order, chunk-
        # pipeline commit, speculate-vs-plain + park) is delegated to
        # the composed ServingPolicy; the default composition is
        # byte-parity with the pre-policy engine.
        if policy is None:
            policy = config.make_policy()
        if drafter is not None and policy.speculation.drafter is None:
            policy.speculation.drafter = drafter
        self.policy = policy
        self.drafter = policy.speculation.drafter
        self.policy.speculation.prepare(self.batch)
        # draft-tree speculation: the shape is policy-owned (the
        # SpeculationPolicy is the seam a learned controller would tune
        # it through); the config field seeds the default policy, and an
        # explicitly-passed policy's width wins.  0 = linear chain.
        self.tree_width = (policy.speculation.tree_width
                           or config.tree_width)
        if self.tree_width:
            T.tree_check(cfg)
        # decoupled-training deploy slot: a callable returning the latest
        # published DraftVersion (or None); polled once per superstep —
        # a host attribute read, zero extra device syncs
        self.deploy_source = deploy_source
        self._deploy_seq = 0
        # >0 enables deploy-time in-place re-seed of resident lanes'
        # draft cache from the rolling capture ring (superstep mode)
        self.reseed_window = (max(config.reseed_window, self.gamma + 2)
                              if config.reseed_window else 0)
        self.gate_arrivals = config.gate_arrivals
        self.completion_sink = config.completion_sink
        self.idle_wait_s = config.idle_wait_s
        # >0 enables chunked refill prefill: prompts are prefilled in
        # fixed-width chunks that interleave with resident supersteps
        # instead of stalling every decode lane for the whole prompt.
        # Must be a multiple of 8 (the refill shape bucket, so the
        # ragged first chunk stays bucketed too).  0 = legacy one-shot.
        if config.prefill_chunk and config.prefill_chunk % 8:
            raise ValueError(f"prefill_chunk {config.prefill_chunk} must "
                             "be a multiple of 8 (refill shape bucket)")
        self.prefill_chunk = config.prefill_chunk
        # per-gap prefill budget in row-tokens: the gap a full batch
        # admitted at once takes at one chunk per pipeline, so spending
        # it when fewer rows prefill never lengthens the worst gap
        # resident lanes see (``_plan_gap``)
        self._gap_budget = self.batch * self.prefill_chunk
        # >0 switches the target + draft caches from dense per-lane
        # buffers to block-table page pools (core/paging.py): lanes
        # reserve pages at admission (the scheduler defers on pool
        # pressure), committed prompt prefixes are COW-shared across
        # lanes, and the host-authoritative block table ships to the
        # device only when it changed.  0 = dense (byte-parity default).
        self.page_size = config.page_size
        self.paged = self.page_size > 0
        self.allocator: Optional[paging.PageAllocator] = None
        self.num_pages = 0
        if self.paged:
            T.paged_check(cfg, self.max_len, self.page_size)
            self.num_pages = (config.num_pages or
                              self.batch * self.max_len // self.page_size)
            self.allocator = paging.PageAllocator(
                self.num_pages, self.page_size, self.batch, self.max_len,
                share_prefix=config.share_prefix)
        self._pipelines: List[_ChunkPipeline] = []
        self._cohort_next = 0
        # host-side parking lot for preempted lanes: per-lane KV + draft
        # rows + superstep state gathered to host-owned device buffers at
        # a superstep boundary, restored when a slot frees up.  Spilling
        # keeps the full capture ring, which is what lets reseed_window
        # coexist with paged serving (the paged re-seed op rewrites the
        # lane's draft rows through its block-table row in place).
        self._spills = paging.SpillStore()
        if self.policy.preemption.enabled and self.superstep_rounds <= 0:
            raise ValueError(
                "preemption requires superstep mode (superstep_rounds > "
                "0): spill/restore only runs at superstep boundaries")
        self._sleep = time.sleep           # injectable for tests
        self._clock = time.perf_counter    # injectable for tests — the
        # single clock domain behind admit_t / first_token_t / finish_t
        # and wall_s, shared with the Scheduler so latency stats never
        # mix real and fake time
        # ---------------------------------------------- observability
        # Host-side instruments only (docs/observability.md): the tracer
        # defaults to DEFAULT_TRACER (records only during a profiler
        # session) and the recorder to the null one, both one check per
        # hook otherwise, and every ServingStats counter lives in
        # the metrics registry (``serving.*``), shared with the training
        # service / allocator when the system layer passes one in.
        self.tracer = tracer if tracer is not None else DEFAULT_TRACER
        self.recorder = recorder if recorder is not None else NULL_RECORDER
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.stats = ServingStats(registry=self.metrics)
        self.policy.speculation.on_transition = self._spec_transition
        self._register_obs_metrics()
        # constant base key for per-request sampling streams: lane keys
        # are fold_in(fold_in(base, sid), step) with sid the request's
        # admission ordinal — identical across scheduling policies
        self._base_key = jax.random.key(config.seed)
        self._sid_next = 0
        self._key = jax.random.key(config.seed)  # legacy chain (probes)
        self._build_steps()

    # ------------------------------------------------------------ jit fns
    def _build_steps(self):
        cfg, dcfg, gamma = self.cfg, self.dcfg, self.gamma

        @jax.jit
        def _prefill(params, tokens, pad):
            return T.prefill(cfg, params, tokens, max_len=self.max_len,
                             pad=pad)

        @jax.jit
        def _seed_draft(params, dparams, dcache, caps, tokens, pad):
            return eagle.seed_prompt_pairs(dcfg, dparams, params["embed"],
                                           dcache, caps, tokens, pad)

        tree_width = self.tree_width

        @jax.jit
        def _spec_step(params, dparams, cache, dcache, carry, keys):
            if tree_width:
                return spec.tree_decode_step(
                    cfg, dcfg, params, dparams, cache, dcache, carry,
                    gamma=gamma, width=tree_width, greedy=self.greedy,
                    keys=keys)
            return spec.spec_decode_step(
                cfg, dcfg, params, dparams, cache, dcache, carry,
                gamma=gamma, greedy=self.greedy, keys=keys)

        @jax.jit
        def _plain_step(params, cache, carry, keys):
            return spec.plain_step_from_carry(cfg, params, cache, carry,
                                              gamma=gamma,
                                              greedy=self.greedy,
                                              keys=keys)

        base_key = self._base_key

        @jax.jit
        def _lane_keys(sids, steps):
            # the per-step loop's host-side twin of the superstep's
            # in-scan key derivation — same fold_in ops, bit-identical
            return jax.vmap(lambda s, c: jax.random.fold_in(
                jax.random.fold_in(base_key, s), c))(sids, steps)

        self._lane_keys_fn = _lane_keys
        # dummy per-lane keys for the jitted step signature under greedy
        # decoding (never consumed)
        self._null_keys = jax.random.split(jax.random.key(0), self.batch)

        @jax.jit
        def _pick_sampled(logits, sids):
            # first-token sampling = per-request stream step 0
            keys = _lane_keys(sids, jnp.zeros_like(sids))
            return jax.vmap(jax.random.categorical)(keys, logits
                                                    ).astype(jnp.int32)

        self._pick_sampled_fn = _pick_sampled

        decay = self._ema

        @jax.jit
        def _ema_step(ema, ell):
            # same compiled f32 mul-add as the superstep's in-scan EMA:
            # numpy emulation differs by an FMA ulp, which could flip an
            # Eq. 5 threshold compare between the two engine modes
            return decay * ema + (1.0 - decay) * ell

        self._prefill_fn = _prefill
        self._seed_fn = _seed_draft
        self._spec_fn = _spec_step
        self._plain_fn = _plain_step
        self._ema_fn = _ema_step

        def _refill_core(params, dparams, cache, dcache, toks, pad, mask,
                         src, sids):
            """Prefill a refill batch of R new prompts and write their
            lanes into the live device state.  ``mask``/``src`` are the
            host-built (B,) lane map (padded refill rows are simply
            never gathered).  Returns the updated (cache, dcache), the
            R-batch prefill carry, and the R first sampled tokens."""
            pre = T.prefill(cfg, params, toks, max_len=self.max_len,
                            pad=pad)
            if self.greedy:
                first = pre["logits"].argmax(-1).astype(jnp.int32)
            else:
                first = _pick_sampled(pre["logits"], sids)
            rdc = eagle.seed_refill_cache(dcfg, dparams, params["embed"],
                                          pre["captures"], toks, pad,
                                          self.max_len)
            if self.paged:
                # paged live state: write the dense staging rows through
                # the lanes' block tables (positions past each lane's
                # reservation route to the trash page, exactly as dense
                # rows keep junk past the valid region)
                cache = spec.scatter_target_cache_paged(cache,
                                                        pre["cache"],
                                                        mask, src)
                dcache = eagle.scatter_draft_rows_paged(dcache, rdc,
                                                        mask, src)
            else:
                cache = spec.scatter_target_cache(cache, pre["cache"],
                                                  mask, src)
                dcache = eagle.scatter_draft_rows(dcache, rdc, mask, src)
            carry_r = spec.init_carry(cfg, dcfg, pre, first, gamma)
            return cache, dcache, carry_r, first

        # the live cache/draft-cache/state buffers are donated on every
        # dispatch: the superstep, refill and re-seed ops update them
        # in place instead of re-allocating the full serving state (and
        # its telemetry buffers) per call
        @functools.partial(jax.jit, donate_argnums=(2, 3, 4))
        def _refill_superstep(params, dparams, cache, dcache, state,
                              max_new, toks, pad, mask, src, budgets,
                              sids):
            cache, dcache, carry_r, first = _refill_core(
                params, dparams, cache, dcache, toks, pad, mask, src,
                sids)
            state = spec.refill_superstep_state(
                state, carry_r, first, budgets, mask, src,
                eos_id=self.eos_id, sids=sids)
            max_new = jnp.where(mask, jnp.take(budgets, src), max_new)
            return cache, dcache, state, max_new, first

        @jax.jit
        def _refill_stepwise(params, dparams, cache, dcache, carry, toks,
                             pad, mask, src, sids):
            cache, dcache, carry_r, first = _refill_core(
                params, dparams, cache, dcache, toks, pad, mask, src,
                sids)
            carry = spec.scatter_carry(carry, carry_r, mask, src)
            return cache, dcache, carry, first

        self._refill_ss_fn = _refill_superstep
        self._refill_step_fn = _refill_stepwise

        # ---- paged-mode ops.  The prologue writes through the block
        # tables like any refill (the dense prologue adopts the prefill
        # cache wholesale, which has no paged equivalent), and a chunk
        # pipeline whose rows all hit the prefix registry seeds its
        # staging straight from the shared pages instead of recomputing
        # the prefix chunks (``_chunk_resume``).
        self._prologue_paged_fn = None
        self._chunk_resume_fn = None
        if self.paged:
            @functools.partial(jax.jit, donate_argnums=(2, 3))
            def _prologue_paged(params, dparams, cache, dcache, toks,
                                pad, sids):
                b = toks.shape[0]
                mask = jnp.ones((b,), bool)
                src = jnp.arange(b, dtype=jnp.int32)
                return _refill_core(params, dparams, cache, dcache,
                                    toks, pad, mask, src, sids)

            self._prologue_paged_fn = _prologue_paged

            @functools.partial(jax.jit, static_argnums=(0, 1))
            def _chunk_resume(width, q, cache, dcache, tbl_rows, pad):
                """Seed a pipeline's staging caches with positions
                [0, q) gathered from shared prefix pages (``tbl_rows``:
                (R, ceil(q / P)) page ids, one row per staging row) —
                the zero-prefill replacement for the prefix's chunks."""
                r = tbl_rows.shape[0]
                cache_s = T.init_cache(cfg, r, width)
                cache_s["lengths"] = jnp.full((r,), q, jnp.int32)
                cache_s["pad"] = pad

                def _fill(s_leaf, pool):
                    rows = jax.vmap(lambda p: paging.gather_rows_paged(
                        p, tbl_rows, q))(pool)
                    return s_leaf.at[:, :, :q].set(
                        rows.astype(s_leaf.dtype))

                for g in cache_s:
                    if g in ("lengths", "pad"):
                        continue
                    cache_s[g] = jax.tree.map(_fill, cache_s[g], cache[g])
                dcache_s = eagle.init_draft_cache(dcfg, r, self.max_len)
                for leaf in ("k", "v"):
                    rows = paging.gather_rows_paged(dcache[leaf],
                                                    tbl_rows, q)
                    dcache_s[leaf] = dcache_s[leaf].at[:, :q].set(
                        rows.astype(dcache_s[leaf].dtype))
                dcache_s["lengths"] = jnp.full((r,), q, jnp.int32)
                dcache_s["pad"] = pad
                return cache_s, dcache_s

            self._chunk_resume_fn = _chunk_resume

        # ---- chunked refill pipeline (prefill_chunk > 0).  A refill's
        # prompt is prefilled chunk by chunk into a *staging* cache pair
        # that only touches the live device state at commit time, so
        # resident decode lanes never wait for more than one chunk of
        # prefill per inter-superstep gap.  The continuation path goes
        # through the decode step, which is bitwise-identical to the
        # one-shot prefill on the valid cache region and the emitted
        # logits (tests/test_chunked_prefill.py pins this, chunked ==
        # one-shot, for random lengths and chunk sizes).
        def _chunk_start_core(params, dparams, toks_c, nxt, pad, adv,
                              width):
            """First (ragged-width) chunk: fresh staging caches.  ``nxt``
            is the lookahead-shifted token slice for the draft pairs;
            ``adv`` the per-lane valid pair count.  The staging target
            cache is allocated at the pipeline's prompt ``width`` (not
            max_len) so continuation chunks attend over the same key
            width the one-shot prefill does — the byte-parity
            requirement (see ``spec.pad_target_cache``)."""
            pre = T.prefill(cfg, params, toks_c, max_len=width, pad=pad)
            dcache_s = eagle.init_draft_cache(dcfg, toks_c.shape[0],
                                              self.max_len)
            dcache_s = eagle.seed_chunk_pairs(
                dcfg, dparams, params["embed"], dict(dcache_s, pad=pad),
                pre["captures"], nxt, adv)
            return (pre["cache"], dcache_s, pre["logits"],
                    pre["captures"][:, -1])

        def _chunk_cont_core(params, dparams, cache_s, dcache_s, toks_c,
                             nxt, adv):
            """Continuation chunk: extend the staging caches through the
            decode path at cache positions [pos, pos + chunk)."""
            r, w = toks_c.shape
            out = T.decode_step(cfg, params, cache_s, toks_c)
            cache_s = T.commit_cache(cfg, out["cache"],
                                     jnp.full((r,), w, jnp.int32))
            dcache_s = eagle.seed_chunk_pairs(
                dcfg, dparams, params["embed"], dcache_s,
                out["captures"], nxt, adv)
            return (cache_s, dcache_s, out["logits"][:, -1],
                    out["captures"][:, -1])

        def _chunk_first_token(logits, sids):
            if self.greedy:
                return logits.argmax(-1).astype(jnp.int32)
            return _pick_sampled(logits, sids)

        def _chunk_scatter_core(staging, cache, dcache, mask, src, sids):
            """The commit recipe both engine modes share (the chunked
            twin of ``_refill_core``'s output handling): sample the
            first token, pad the staging target cache out to the live
            geometry, scatter both staging caches into the masked live
            lanes, and build the refill carry.  Returns
            (cache, dcache, carry_r, first)."""
            cache_s, dcache_s, logits, caps_last = staging
            first = _chunk_first_token(logits, sids)
            cache_s = spec.pad_target_cache(
                cache_s, None if self.paged else
                T.cache_abstract(cfg, caps_last.shape[0], self.max_len))
            if self.paged:
                cache = spec.scatter_target_cache_paged(cache, cache_s,
                                                        mask, src)
                dcache = eagle.scatter_draft_rows_paged(dcache, dcache_s,
                                                        mask, src)
            else:
                cache = spec.scatter_target_cache(cache, cache_s, mask,
                                                  src)
                dcache = eagle.scatter_draft_rows(dcache, dcache_s, mask,
                                                  src)
            carry_r = spec.init_carry_from_caps(caps_last, first, gamma)
            return cache, dcache, carry_r, first

        def _chunk_commit_core(staging, cache, dcache, state, max_new,
                               mask, src, budgets, sids):
            """Commit a fully-prefilled staging pair into the live state
            and reset the lanes' superstep carry — the chunked twin of
            ``_refill_superstep``."""
            cache, dcache, carry_r, first = _chunk_scatter_core(
                staging, cache, dcache, mask, src, sids)
            state = spec.refill_superstep_state(
                state, carry_r, first, budgets, mask, src,
                eos_id=self.eos_id, sids=sids)
            max_new = jnp.where(mask, jnp.take(budgets, src), max_new)
            return cache, dcache, state, max_new, first

        @functools.partial(jax.jit, static_argnums=(0,))
        def _chunk_start(width, params, dparams, toks_c, nxt, pad, adv):
            return _chunk_start_core(params, dparams, toks_c, nxt, pad,
                                     adv, width)

        @functools.partial(jax.jit, donate_argnums=(2, 3))
        def _chunk_cont(params, dparams, cache_s, dcache_s, toks_c, nxt,
                        adv):
            return _chunk_cont_core(params, dparams, cache_s, dcache_s,
                                    toks_c, nxt, adv)

        @functools.partial(jax.jit, donate_argnums=(2, 3, 4))
        def _chunk_commit(params, dparams, cache, dcache, state, max_new,
                          cache_s, dcache_s, logits, caps_last, mask,
                          src, budgets, sids):
            """Standalone commit for a staged pipeline waiting on its
            cohort (its final chunk already ran unfused)."""
            return _chunk_commit_core((cache_s, dcache_s, logits,
                                       caps_last), cache, dcache, state,
                                      max_new, mask, src, budgets, sids)

        # final-chunk ops fuse the last prefill chunk with its commit —
        # one dispatch per pipeline completion, so a single-chunk
        # pipeline costs exactly one op, like a one-shot refill
        @functools.partial(jax.jit, static_argnums=(0,),
                           donate_argnums=(7, 8, 9))
        def _chunk_final_start(width, params, dparams, toks_c, nxt, pad,
                               adv, cache, dcache, state, max_new, mask,
                               src, budgets, sids):
            staging = _chunk_start_core(params, dparams, toks_c, nxt,
                                        pad, adv, width)
            return _chunk_commit_core(staging, cache, dcache, state,
                                      max_new, mask, src, budgets, sids)

        # staging args are not donated: the commit pads them to max_len,
        # so their buffers can never be reused for an output
        @functools.partial(jax.jit, donate_argnums=(7, 8, 9))
        def _chunk_final_cont(params, dparams, cache_s, dcache_s, toks_c,
                              nxt, adv, cache, dcache, state, max_new,
                              mask, src, budgets, sids):
            staging = _chunk_cont_core(params, dparams, cache_s,
                                       dcache_s, toks_c, nxt, adv)
            return _chunk_commit_core(staging, cache, dcache, state,
                                      max_new, mask, src, budgets, sids)

        @jax.jit
        def _chunk_commit_step(params, dparams, cache, dcache, carry,
                               cache_s, dcache_s, logits, caps_last,
                               mask, src, sids):
            """Final-chunk commit for the per-step reference loop (kept
            unfused — the stepwise loop is the parity oracle, not a hot
            path; the commit recipe is the shared ``_chunk_scatter_core``,
            so the two modes cannot drift)."""
            cache, dcache, carry_r, first = _chunk_scatter_core(
                (cache_s, dcache_s, logits, caps_last), cache, dcache,
                mask, src, sids)
            carry = spec.scatter_carry(carry, carry_r, mask, src)
            return cache, dcache, carry, first

        self._chunk_start_fn = _chunk_start
        self._chunk_cont_fn = _chunk_cont
        self._chunk_commit_ss_fn = _chunk_commit
        self._chunk_final_start_fn = _chunk_final_start
        self._chunk_final_cont_fn = _chunk_final_cont
        self._chunk_commit_step_fn = _chunk_commit_step

        self._superstep_fn = None
        if self.superstep_rounds > 0:
            # default table for direct callers (tests/bench probes that
            # dispatch the compiled fn themselves); the serving loop
            # passes the SpeculationPolicy's per-dispatch table — the
            # Eq. 5 gate, or its park/probe variants, all the same
            # shape/dtype so one compiled trace serves every mode
            default_table = self.policy.speculation.dispatch_table()
            ss = functools.partial(
                spec.decode_superstep, cfg, dcfg,
                rounds=self.superstep_rounds, gamma=gamma,
                greedy=self.greedy, ema_decay=self._ema,
                eos_id=self.eos_id,
                collect_signals=self.extractor is not None,
                tree_width=self.tree_width)

            @functools.partial(jax.jit, donate_argnums=(2, 3, 4))
            def _superstep(params, dparams, cache, dcache, state, max_new,
                           table=default_table):
                return ss(params, dparams, cache, dcache, state, max_new,
                          table)

            self._superstep_fn = _superstep

        self._reseed_fn = None
        if self.reseed_window and self.superstep_rounds > 0:
            # the embeddings go in as an argument: a closed-over array
            # would be baked into the program as a constant
            if self.paged:
                @functools.partial(jax.jit, donate_argnums=(2,))
                def _reseed(embed, dparams, dcache, state):
                    return eagle.reseed_draft_rows_from_ring_paged(
                        dcfg, dparams, embed, dcache,
                        state.cap_feats, state.cap_toks, state.cap_count,
                        self.max_len)
            else:
                @functools.partial(jax.jit, donate_argnums=(2,))
                def _reseed(embed, dparams, dcache, state):
                    return eagle.reseed_draft_rows_from_ring(
                        dcfg, dparams, embed, dcache,
                        state.cap_feats, state.cap_toks, state.cap_count)

            self._reseed_fn = (lambda dparams, dcache, state: _reseed(
                self.params["embed"], dparams, dcache, state))

        # ---- preemption spill/restore ops (superstep mode).  Spill
        # gathers one lane's full serving state — target-cache rows,
        # draft rows, superstep state slice, remaining budget — into
        # fresh host-owned device buffers (non-donating, so it is safe
        # to enqueue behind an in-flight superstep that still reads the
        # live buffers).  Restore writes the slices back into a freed
        # lane; under paging it writes *through the lane's new
        # block-table row*, so the physical pages may differ while the
        # logical rows are bit-identical.  Both ops take the lane as a
        # traced scalar: one compiled trace covers every slot.
        self._spill_fn = None
        self._restore_fn = None
        if self.superstep_rounds > 0:
            paged = self.paged
            page_size = self.page_size
            max_len = self.max_len

            def _state_slices(state, lane):
                st = {
                    "feats": state.carry.feats[lane],
                    "tokens": state.carry.tokens[lane],
                    "advance": state.carry.advance[lane],
                    "active": state.active[lane],
                    "gen_count": state.gen_count[lane],
                    "sid": state.sid[lane],
                    "step_idx": state.step_idx[lane],
                }
                if state.cap_feats is not None:
                    st["cap_feats"] = state.cap_feats[lane]
                    st["cap_toks"] = state.cap_toks[lane]
                    st["cap_count"] = state.cap_count[lane]
                return st

            @jax.jit
            def _spill(cache, dcache, state, max_new, lane):
                if paged:
                    trow = cache["page_tbl"][lane]

                    def _pool_lane(pool):
                        # pool leaf (S, pages+1, P, ...) -> (S, max_len, ...)
                        return jax.vmap(lambda p: paging.gather_view(
                            p, trow[None])[0])(pool)

                    cslices = {g: jax.tree.map(_pool_lane, cache[g])
                               for g in cache
                               if g not in ("lengths", "pad", "page_tbl")}
                    dtrow = dcache["tbl"][lane]
                    dk = paging.gather_view(dcache["k"], dtrow[None])[0]
                    dv = paging.gather_view(dcache["v"], dtrow[None])[0]
                else:
                    cslices = {g: jax.tree.map(lambda leaf: leaf[:, lane],
                                               cache[g])
                               for g in cache if g not in ("lengths", "pad")}
                    dk = dcache["k"][lane]
                    dv = dcache["v"][lane]
                return {
                    "cache": cslices,
                    "clen": cache["lengths"][lane],
                    "cpad": cache["pad"][lane],
                    "dk": dk, "dv": dv,
                    "dlen": dcache["lengths"][lane],
                    "dpad": dcache["pad"][lane],
                    "state": _state_slices(state, lane),
                    "budget": max_new[lane],
                }

            @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
            def _restore(cache, dcache, state, max_new, lane, sp):
                cache = dict(cache)
                dcache = dict(dcache)
                if paged:
                    trow = cache["page_tbl"][lane]
                    pos = jnp.arange(max_len, dtype=jnp.int32)[None, :]

                    def _write1(p, r):
                        # rows past the lane's (re-)reservation route to
                        # the trash page: unmapped table entries hold the
                        # trash id, so page_slot needs no masking here
                        page, slot = paging.page_slot(trow[None], page_size,
                                                      pos, p.shape[0] - 1)
                        return p.at[page[0], slot[0]].set(r.astype(p.dtype))

                    for g in list(cache):
                        if g in ("lengths", "pad", "page_tbl"):
                            continue
                        cache[g] = jax.tree.map(
                            lambda pool, rows: jax.vmap(_write1)(pool, rows),
                            cache[g], sp["cache"][g])
                    dtrow = dcache["tbl"][lane]

                    def _dwrite(p, r):
                        page, slot = paging.page_slot(dtrow[None], page_size,
                                                      pos, p.shape[0] - 1)
                        return p.at[page[0], slot[0]].set(r.astype(p.dtype))

                    dcache["k"] = _dwrite(dcache["k"], sp["dk"])
                    dcache["v"] = _dwrite(dcache["v"], sp["dv"])
                else:
                    for g in list(cache):
                        if g in ("lengths", "pad"):
                            continue
                        cache[g] = jax.tree.map(
                            lambda leaf, s: leaf.at[:, lane].set(
                                s.astype(leaf.dtype)),
                            cache[g], sp["cache"][g])
                    dcache["k"] = dcache["k"].at[lane].set(sp["dk"])
                    dcache["v"] = dcache["v"].at[lane].set(sp["dv"])
                cache["lengths"] = cache["lengths"].at[lane].set(sp["clen"])
                cache["pad"] = cache["pad"].at[lane].set(sp["cpad"])
                dcache["lengths"] = dcache["lengths"].at[lane].set(
                    sp["dlen"])
                dcache["pad"] = dcache["pad"].at[lane].set(sp["dpad"])
                st = sp["state"]
                carry = state.carry._replace(
                    feats=state.carry.feats.at[lane].set(st["feats"]),
                    tokens=state.carry.tokens.at[lane].set(st["tokens"]),
                    advance=state.carry.advance.at[lane].set(st["advance"]))
                kw = {}
                if state.cap_feats is not None:
                    kw = dict(
                        cap_feats=state.cap_feats.at[lane].set(
                            st["cap_feats"]),
                        cap_toks=state.cap_toks.at[lane].set(st["cap_toks"]),
                        cap_count=state.cap_count.at[lane].set(
                            st["cap_count"]))
                state = state._replace(
                    carry=carry,
                    active=state.active.at[lane].set(st["active"]),
                    gen_count=state.gen_count.at[lane].set(st["gen_count"]),
                    sid=state.sid.at[lane].set(st["sid"]),
                    step_idx=state.step_idx.at[lane].set(st["step_idx"]),
                    **kw)
                max_new = max_new.at[lane].set(sp["budget"])
                return cache, dcache, state, max_new

            self._spill_fn = _spill
            self._restore_fn = _restore

    def adopt_compiled(self, donor: "ServingEngine"):
        """Share the donor's jitted step functions (fleet replicas).

        A data-parallel replica fleet (``repro.fleet.router``) runs N
        engines with the *same* model, params, and serving config —
        their ``_build_steps`` closures trace to identical computations,
        so compiling them N times is pure waste.  This replaces every
        ``*_fn`` attribute (and the shared null-key constant) with the
        donor's, so XLA traces/compiles once per fleet.  Safe only when
        every trace-time capture matches: same model/draft configs, the
        same params object (``_reseed_fn`` reads ``params['embed']``),
        and an equal ServingConfig minus the per-replica
        ``completion_sink`` (equal seed ⇒ equal baked-in base sampling
        key, so sampled streams stay request-keyed and replica-
        invariant)."""
        if donor is self:
            return
        mine = dataclasses.replace(self.config, completion_sink=None)
        theirs = dataclasses.replace(donor.config, completion_sink=None)
        if (self.cfg != donor.cfg or self.dcfg != donor.dcfg
                or mine != theirs):
            raise ValueError("adopt_compiled needs identically-configured "
                             "engines (model, draft, ServingConfig)")
        if self.params is not donor.params:
            raise ValueError("adopt_compiled needs the shared params "
                             "object (closures capture params['embed'])")
        for name, fn in donor.__dict__.items():
            if name.endswith("_fn") or name == "_null_keys":
                setattr(self, name, fn)

    def deploy_draft(self, dparams):
        """Hot-swap the draft (no target reload — TIDE's C2).  Under
        ``serve_stream`` the swap lands between supersteps, mid-stream.

        Caveat: without a capture ring (``reseed_window=0``), lanes
        resident at swap time keep draft-cache K/V built by the *old*
        draft until they retire.  Token streams stay correct — the
        target verifies every draft — but those lanes' acceptance length
        may dip until refilled.  With ``reseed_window>0`` the engine
        re-seeds resident lanes' trailing draft K/V from the rolling
        capture ring at deploy time (superstep mode), so the new draft's
        acceptance gain applies immediately."""
        self.dparams = dparams

    def _poll_deploy(self, source=None):
        """Pick up a freshly published draft version, if any (one host
        attribute read per superstep — the zero-sync deploy path).
        ``source`` overrides the engine's own ``deploy_source`` — the
        TIDE system's synchronous mode pushes through here too, so both
        modes share one pickup protocol."""
        source = source or self.deploy_source
        if source is None:
            return None
        ver = source()
        if ver is None or ver.seq <= self._deploy_seq:
            return None
        self._deploy_seq = ver.seq
        self.dparams = ver.dparams
        self.stats.deploys += 1
        if self.tracer.enabled:
            self.tracer.instant("deploy", seq=ver.seq)
        if self.recorder.enabled:
            self.recorder.global_event("deploy", round_=self.stats.steps,
                                       seq=ver.seq)
        return ver

    def reset_adaptation(self, dparams):
        """Back to the post-construction adaptive state (draft params,
        acceptance EMA, deploy/sid counters, stats); compiled functions
        stay warm."""
        self.dparams = dparams
        self.accept_ema = 1.0
        self._deploy_seq = 0
        self._sid_next = 0
        self._pipelines = []
        self._cohort_next = 0
        self._spills = paging.SpillStore()
        if self.allocator is not None:
            self.allocator.reset()
        self.stats = ServingStats(registry=self.metrics)
        self.policy.speculation.reset()
        self.policy.speculation.on_transition = self._spec_transition
        if self.drafter is not None:
            self.drafter.enabled = True

    # -------------------------------------------------- observability
    def _register_obs_metrics(self):
        """Declare the ``spec.*`` and ``paging.*`` namespaces as
        callback gauges over live policy/allocator state — evaluated
        only at ``snapshot()`` time, so they cost nothing per round."""
        reg = self.metrics
        sp = self.policy.speculation
        reg.gauge("spec.parks", fn=lambda: sp.parks)
        reg.gauge("spec.resumes", fn=lambda: sp.resumes)
        reg.gauge("spec.parked", fn=lambda: int(sp.parked))
        reg.gauge("spec.probing", fn=lambda: int(sp.probing))
        reg.gauge("spec.tree_width", fn=lambda: self.tree_width)
        reg.gauge("spec.gamma", fn=lambda: self.gamma)
        reg.gauge("spec.accept_ema", fn=lambda: self.accept_ema)
        reg.gauge("serving.spilled_requests", fn=lambda: len(self._spills))
        if self.allocator is not None:
            self.allocator.register_metrics(reg)
        else:
            # dense engines still expose the namespace (all zero)
            for name in ("paging.pages_in_use", "paging.pages_free",
                         "paging.pages_peak", "paging.prefix_hits",
                         "paging.prefix_tokens_saved", "paging.evictions",
                         "paging.cow_forks", "paging.spilled_pages"):
                reg.gauge(name)

    def _spec_transition(self, kind: str, fields: dict):
        """Speculation park/probe/resume hook (host-side, from
        ``observe_round``/``step_decision`` telemetry replay)."""
        if self.tracer.enabled:
            self.tracer.instant(kind, **fields)
        if self.recorder.enabled:
            self.recorder.global_event(kind, round_=self.stats.steps,
                                       **fields)

    def _next_key(self):
        self._key, k = jax.random.split(self._key)
        return k

    def _assign_sids(self, admitted):
        """Stamp admitted requests with their sampling-stream id — the
        engine-lifetime admission ordinal, identical for a given
        admission order (the policy's) across engine modes — and the
        deterministic admission round (the TTFT round-clock origin)."""
        for _, r in admitted:
            if r.admit_round is None:
                r.admit_round = self.stats.steps
            if r.sid is None:
                r.sid = self._sid_next
                self._sid_next += 1
                if self.recorder.enabled:
                    self.recorder.admit(r, self.stats.steps)

    def _apply_capture_park(self):
        """Parked speculation parks signal capture with it; on resume
        the controller (when present) re-drives ``extractor.enabled``
        each round, otherwise the park control owns it and must restore
        capture itself.  No-op unless the park control is on — default
        engines keep controller/extractor semantics untouched."""
        if self.extractor is None or not self.policy.speculation.park_patience:
            return
        if self.policy.speculation.blocks_capture:
            self.extractor.enabled = False
        elif self.controller is None:
            self.extractor.enabled = True

    def _idle_tick(self, wait: Optional[float]):
        """No admissible work but the gated stream has future arrivals:
        emit an idle superstep — no dispatch, just a bounded host sleep
        that yields the core to the decoupled draft trainer (this slack
        is exactly what the single-device async-training fallback
        consumes)."""
        self.stats.idle_supersteps += 1
        with self.tracer.span("idle"):
            self._sleep(min(max(wait or 0.0, 0.0), self.idle_wait_s))

    # -------------------------------------------------- request accounting
    def _finish(self, r: Request):
        if r.finish_t is None:
            r.finish(self._clock())
            r.finish_round = self.stats.steps    # deterministic stamp
            self.stats.completed += 1
            if r.latency is not None:
                self.stats.record_latency(r.latency)
            if self.recorder.enabled:
                self.recorder.finish(r, self.stats.steps)

    def _commit_first(self, r: Request, tok: int):
        """Commit a freshly (pre)filled slot's first sampled token."""
        if r.finish_t is not None:       # inert padding / pre-finished
            return
        if r.max_new_tokens < 1:
            self._finish(r)
            return
        r.generated.append(tok)
        if r.first_token_t is None:
            r.first_token_t = self._clock()
            r.first_token_round = self.stats.steps
            self.stats.record_ttft(r.ttft)
            if self.recorder.enabled:
                self.recorder.note(r.rid, "first_token",
                                   round_=self.stats.steps)
        self.stats.tokens_out += 1
        if self.eos_id is not None and tok == self.eos_id:
            self._finish(r)

    def _commit_firsts(self, fdev, admitted):
        """Pull a refill's first tokens to the host (the wait is the
        ``refill.first_wait`` span) and commit them, row by row, to the
        admitted ``(slot, request)`` pairs."""
        with self.tracer.span("refill.first_wait"):
            first_np = np.asarray(fdev)
        for row, (_, req) in enumerate(admitted):
            self._commit_first(req, int(first_np[row]))
        if self.tracer.enabled:
            self.tracer.instant("serve.deliver", rids=[
                r.rid for _, r in admitted if r.first_token_t is not None])

    # ------------------------------------------------------------- prologue
    def _prologue(self, requests: List[Request]):
        """Pad + prefill + draft seed for one full batch of B slots.
        Returns the initial device serving state (cache, dcache, carry,
        first_token)."""
        b = self.batch
        plen = max(len(r.prompt) for r in requests)
        toks = np.zeros((b, plen), np.int32)
        pad = np.zeros((b,), np.int32)
        for i, r in enumerate(requests):
            pad[i] = plen - len(r.prompt)
            toks[i, pad[i]:] = r.prompt
        toks_j, pad_j = jnp.asarray(toks), jnp.asarray(pad)
        self._note_prefill_op(b, plen)
        self.stats.prefill_gap_tokens.add(b * plen)
        if self.paged:
            # page-pool state: reserve lanes (inert padding slots are
            # skipped — they are not scheduler-owned, so nothing would
            # ever free them), write the batch prefill through the
            # tables, then publish the prompt prefixes
            group = [(i, r) for i, r in enumerate(requests)
                     if r.finish_t is None]
            self._reserve_group(group, plen)
            cache = T.init_cache(self.cfg, b, self.max_len,
                                 page_size=self.page_size,
                                 num_pages=self.num_pages)
            dcache = eagle.init_draft_cache(self.dcfg, b, self.max_len,
                                            page_size=self.page_size,
                                            num_pages=self.num_pages)
            cache, dcache = self._ship_tables(cache, dcache)
            cache, dcache, carry, first = self._prologue_paged_fn(
                self.params, self.dparams, cache, dcache, toks_j, pad_j,
                jnp.asarray(self._slot_sids(requests)))
            self._publish_prefixes(self._prefix_entries(group, b, plen))
            return cache, dcache, carry, first
        pre = self._prefill_fn(self.params, toks_j, pad_j)
        first = self._pick(pre["logits"], self._slot_sids(requests))
        cache = pre["cache"]
        dcache = eagle.init_draft_cache(self.dcfg, b, self.max_len)
        dcache = self._seed_fn(self.params, self.dparams, dcache,
                               pre["captures"], toks_j, pad_j)
        carry = spec.init_carry(self.cfg, self.dcfg, pre, first, self.gamma)
        return cache, dcache, carry, first

    # ------------------------------------------------------------- serving
    def serve_wave(self, requests: List[Request]) -> List[Request]:
        """Serve one wave to completion (compat wrapper over
        ``serve_stream``).  Waves smaller than the engine batch are
        padded internally with inert zero-budget slots.  Mutates and
        returns the requests."""
        assert len(requests) <= self.batch, \
            f"wave of {len(requests)} exceeds engine batch {self.batch}"
        self.serve_stream(requests)
        return requests

    @staticmethod
    def _slot_sids(requests) -> np.ndarray:
        return np.asarray([INERT_SID if (r is None or r.sid is None)
                           else r.sid for r in requests], np.int32)

    def serve_stream(self, requests: Iterable[Request], *,
                     on_complete: Optional[Callable[[Request], None]] = None
                     ) -> List[Request]:
        """Serve an entire request stream with in-flight slot refill.

        Pulls lazily from ``requests`` (any iterable), keeps the device
        state resident, and refills slots as requests finish.
        ``on_complete`` fires on the host once per finished request (at
        telemetry-drain boundaries) — the TIDE system's synchronous
        training mode uses it to poll the training service.  Returns the
        completed requests in completion order (empty when a
        ``completion_sink`` streams them out instead)."""
        sched = Scheduler(self.batch, requests,
                          policy=self.policy.admission,
                          gate_arrivals=self.gate_arrivals,
                          clock=self._clock,
                          completion_sink=self.completion_sink,
                          admission_guard=(self._admission_guard
                                           if self.paged else None),
                          tracer=self.tracer)
        t0 = self._clock()
        while not sched.has_work():
            wait = sched.next_arrival_in()
            if wait is None:
                return sched.completed
            self._idle_tick(wait)       # gated stream not yet begun
        with self.tracer.span("serve.start"):
            admitted = sched.admit()
            self._assign_sids(admitted)
            reqs0 = [r if r is not None else inert_request()
                     for r in sched.slots]
            if self.prefill_chunk:
                # chunked prefill: no one-shot prologue — the initial
                # batch flows through the same chunk pipelines as every
                # later refill, so no prompt ever stalls the engine for
                # more than one gap's prefill budget
                cache, dcache, carry, first = self._empty_state()
                self._pipelines = []
                self._spawn_pipelines(admitted)
            else:
                with self.tracer.span("prefill.prologue", rows=self.batch):
                    cache, dcache, carry, first = self._prologue(reqs0)
                self._commit_firsts(first, list(enumerate(reqs0)))
        if self._superstep_fn is not None:
            self._stream_superstep(sched, reqs0, cache, dcache, carry,
                                   first, t0, on_complete,
                                   cold=bool(self.prefill_chunk))
        else:
            self._stream_stepwise(sched, cache, dcache, carry, t0,
                                  on_complete,
                                  cold=bool(self.prefill_chunk))
        if self.extractor is not None:
            with self.tracer.span("signals.flush"):
                self.extractor.flush()
        self.stats.wall_s += self._clock() - t0
        return sched.completed

    def _retire_and_admit(self, sched: Scheduler, on_complete):
        """Release finished slots, then admit pending requests into them.
        Returns the new (slot, request) assignments to refill."""
        if self.paged:
            self._free_finished_lanes(sched)
        for r in sched.release_finished():
            if on_complete is not None:
                on_complete(r)
        self._shed_queue(sched, on_complete)
        admitted = sched.admit()
        self._assign_sids(admitted)
        return admitted

    # ------------------------------------------- overload boundary
    # (docs/overload.md).  One host-side pass per superstep boundary:
    # retire finished lanes, drop spill entries that finished via
    # in-flight telemetry, shed hopeless queue entries, restore spilled
    # requests whose effective deadline beats the queue head, admit,
    # preempt-and-admit when the admission tier defers a tighter
    # candidate against a full batch, then hand leftover free lanes to
    # any remaining spilled requests.  Zero added device syncs: spills
    # gather to host-owned device buffers and restores write back
    # through donated ops, both enqueued behind the in-flight superstep.
    def _overload_boundary(self, sched: Scheduler, on_complete, cache,
                           dcache, state, max_new):
        """Superstep-mode twin of ``_retire_and_admit`` that also runs
        the spill/restore + preemption machinery.  Returns the updated
        device bindings plus the new (slot, request) refill
        assignments."""
        if self.paged:
            self._free_finished_lanes(sched)
        for r in sched.release_finished():
            if on_complete is not None:
                on_complete(r)
        if self._spills:
            self._drop_finished_spills(sched, on_complete)
        self._shed_queue(sched, on_complete)
        if self._spills:
            cache, dcache, state, max_new = self._restore_spilled(
                sched, cache, dcache, state, max_new, rank_queue=True)
        admitted = sched.admit()
        if self.policy.preemption.enabled and sched.has_pending():
            admitted += self._preempt_admit(
                sched, cache, dcache, state, max_new,
                {id(r) for _, r in admitted})
        if self._spills:
            cache, dcache, state, max_new = self._restore_spilled(
                sched, cache, dcache, state, max_new, rank_queue=False)
        self._assign_sids(admitted)
        return cache, dcache, state, max_new, admitted

    def _shed_queue(self, sched: Scheduler, on_complete):
        """Load shedding: let the shed policy drop queued requests that
        are not worth serving (expired deadlines, queue overflow).  Shed
        requests finish immediately with whatever they generated
        (nothing, for queued ones) and route through the normal
        completion path.  The default ``none`` policy never touches the
        scheduler, keeping the byte-parity baseline exact."""
        pol = self.policy.preemption.shed
        if pol.name == "none":
            return
        victims = pol.pick(sched.queue_view(), self.stats.steps)
        if not victims:
            return
        for r in victims:
            r.shed = True
            self.stats.shed_requests += 1
            self._finish(r)
        sched.shed(victims)
        for r in victims:
            if on_complete is not None:
                on_complete(r)

    def _drop_finished_spills(self, sched: Scheduler, on_complete):
        """A spilled request can finish *while parked*: the superstep in
        flight at spill time still carried its lane, so its final
        tokens/EOS commit from that superstep's telemetry.  Its pages
        were already freed at spill — just drop the entry and route the
        request through the completion path the scheduler would have
        used."""
        for e in list(self._spills.pending()):
            if e.request.finish_t is not None:
                self._spills.drop(e.request.rid)
                sched.retire(e.request)
                if on_complete is not None:
                    on_complete(e.request)

    @staticmethod
    def _edl(r: Request):
        """Effective-deadline sort key (tightest first), matching the
        loose-ness order the preemption policy victimizes by."""
        return (r.deadline if r.deadline is not None else float("inf"),
                -r.priority)

    def _restore_spilled(self, sched: Scheduler, cache, dcache, state,
                         max_new, *, rank_queue: bool):
        """Move spilled requests back onto free lanes.  With
        ``rank_queue`` (the pre-admission pass) only entries whose
        effective deadline is at least as tight as the queue head's may
        claim a lane — a restored request must never starve a tighter
        queued candidate; the post-admission pass hands out whatever
        lanes are still free.  Restored lanes resume mid-stream: the
        spilled superstep state re-enters the next dispatch exactly
        where the lane left off, so the token stream is byte-identical
        to a never-evicted run."""
        free = [i for i, s in enumerate(sched.slots) if s is None]
        if not free or not self._spills:
            return cache, dcache, state, max_new
        entries = sorted(self._spills.pending(),
                         key=lambda e: self._edl(e.request))
        if rank_queue:
            head = sched.peek_next()
            if head is not None:
                hd = self._edl(head)
                entries = [e for e in entries if self._edl(e.request) <= hd]
        for slot in free:
            if not entries:
                break
            e = entries[0]
            if self.paged:
                if not self.allocator.reserve(slot,
                                              e.pages * self.page_size):
                    break        # pool pressure: keep the entry parked
                self._sync_paged_stats()
                # the restore op writes through the lane's fresh table
                # row, so the table must ship before dispatch
                cache, dcache = self._ship_tables(cache, dcache)
            entries.pop(0)
            self._spills.pop(e.request.rid)
            with self.tracer.span("preempt.restore", rid=e.request.rid,
                                  slot=slot):
                cache, dcache, state, max_new = self._restore_fn(
                    cache, dcache, state, max_new, jnp.int32(slot),
                    e.slices)
            sched.slots[slot] = e.request
            self.stats.restores += 1
            if self.recorder.enabled:
                self.recorder.note(e.request.rid, "restore",
                                   round_=self.stats.steps, slot=slot)
        return cache, dcache, state, max_new

    def _victim_candidates(self, sched: Scheduler, new_ids):
        """Residents eligible for preemption: decoding lanes only —
        never this boundary's admissions (their device state is a refill
        op that has not been built yet), never lanes mid-chunk-prefill
        (their state lives in pipeline staging, not the live buffers)."""
        in_pipe = {id(r) for pl in self._pipelines for _, r in pl.admitted}
        out = []
        for slot, r in enumerate(sched.slots):
            if r is None or r.finish_t is not None:
                continue
            if id(r) in new_ids or id(r) in in_pipe:
                continue
            if r.first_token_t is None:
                continue
            out.append((slot, r))
        return out

    def _spill_victim(self, sched: Scheduler, slot: int, cache, dcache,
                      state, max_new):
        """Evict one resident lane into the SpillStore.  The gather op
        reads the *current* (post-drain) host bindings — which already
        include the in-flight superstep's progress for this lane, whose
        tokens commit at the next drain through the pending record's
        request reference — so the spilled state and the host token
        stream stay exactly in phase."""
        req = sched.slots[slot]
        with self.tracer.span("preempt.spill", rid=req.rid, slot=slot):
            slices = self._spill_fn(cache, dcache, state, max_new,
                                    jnp.int32(slot))
        pages = 0
        if self.paged:
            pages = self.allocator.spill_lane(slot)
        self._spills.put(paging.SpilledLane(req, slices, pages))
        sched.evict(slot)
        req.evictions += 1
        self.stats.preemptions += 1
        if self.recorder.enabled:
            self.recorder.note(req.rid, "preempt",
                               round_=self.stats.steps, slot=slot)

    def _preempt_admit(self, sched: Scheduler, cache, dcache, state,
                       max_new, new_ids):
        """Deadline preemption: while the batch is full and the
        admission tier holds a tighter-deadline candidate at the queue
        head, ask the preemption policy for a victim among the resident
        lanes, spill it, and admit into the freed slot.  Stops as soon
        as the policy declines (no resident is loose enough) or the
        admission guard defers the candidate anyway."""
        pol = self.policy.preemption
        out: List[Tuple[int, Request]] = []
        evicted = 0
        while (sched.has_pending() and evicted < self.batch
               and all(s is not None for s in sched.slots)):
            cand = sched.peek_next()
            if cand is None:
                break
            victim = pol.select_victim(
                cand, self._victim_candidates(sched, new_ids),
                self.stats.steps)
            if victim is None:
                break
            self._spill_victim(sched, victim, cache, dcache, state,
                               max_new)
            evicted += 1
            got = sched.admit()
            if not got:
                break       # guard deferred: lane stays free for restore
            out += got
            new_ids |= {id(r) for _, r in got}
        return out

    def _refill_arrays(self, admitted: List[Tuple[int, Request]]):
        """Host-side packing of a refill batch, shape-bucketed to bound
        jit retraces to (log2 B widths) x (few prompt-length buckets):
        the row count is padded to the next power of two (pad rows
        replicate row 0 and are never gathered — the (B,) mask/src lane
        map is built here, so they cannot touch live state) and the
        prompt width to a multiple of 8 (which also guarantees >=2
        columns for the draft seed)."""
        plen = max(len(r.prompt) for _, r in admitted)
        plen = max(8, -(-plen // 8) * 8)
        n = len(admitted)
        width = 1
        while width < n:
            width *= 2
        toks = np.zeros((width, plen), np.int32)
        pad = np.zeros((width,), np.int32)
        budgets = np.zeros((width,), np.int32)
        sids = np.full((width,), INERT_SID, np.int32)
        for row, (_, r) in enumerate(admitted):
            pad[row] = plen - len(r.prompt)
            toks[row, pad[row]:] = r.prompt
            budgets[row] = r.max_new_tokens
            sids[row] = r.sid
        toks[n:] = toks[0]
        pad[n:] = pad[0]
        mask = np.zeros((self.batch,), bool)
        src = np.zeros((self.batch,), np.int32)
        for row, (slot, _) in enumerate(admitted):
            mask[slot] = True
            src[slot] = row
        return (jnp.asarray(toks), jnp.asarray(pad), jnp.asarray(mask),
                jnp.asarray(src), jnp.asarray(budgets),
                jnp.asarray(sids))

    # ------------------------------------------------- paged KV plumbing
    def _ship_tables(self, cache, dcache):
        """Publish the host-authoritative block table to the device iff
        it changed since the last ship — two separate snapshots, because
        the target and draft caches are donated independently and must
        not share a buffer.  A host-side dict replace: no jitted op ever
        takes the table as an argument, so reservations and frees never
        retrace anything.  No-op on dense engines."""
        if self.allocator is not None and self.allocator.dirty:
            cache = dict(cache, page_tbl=self.allocator.table_device())
            dcache = dict(dcache, tbl=self.allocator.table_device())
            self.allocator.dirty = False
        return cache, dcache

    def _reservation(self, width: int, req: Request) -> int:
        """Token reservation for one lane: prompt width plus the decode
        budget plus the superstep overshoot (a verify round scatters
        the whole draft block's candidate K/V rows — gamma + 1 for the
        linear chain, tree_width * gamma + 1 for a draft tree — past
        the committed length before the accept masks land; the tree
        commit then compacts the accepted branch back into the chain
        layout, so only the block rows themselves ever overshoot)."""
        block = self.gamma * max(self.tree_width, 1) + 1
        return width + req.max_new_tokens + block

    def _admission_guard(self, req: Request,
                         accepted: List[Request]) -> bool:
        """Scheduler admission veto: would this round's already-accepted
        requests plus ``req`` all fit the page pool?  Conservative — the
        width charged is the widest bucketed refill width among the
        candidates (co-admitted one-shot refills all pad to it; chunked
        groups split by bucket and only get narrower), so the estimate
        can only over-count.  A deferred request stays queued in policy
        order and retries once lanes retire."""
        cands = accepted + [req]
        wmax = max(max(8, -(-len(r.prompt) // 8) * 8) for r in cands)
        need = sum(self.allocator.pages_for(self._reservation(wmax, r))
                   for r in cands)
        if self.allocator.can_fit(need):
            return True
        self.stats.admission_deferrals += 1
        if self.recorder.enabled:
            self.recorder.global_event("admission_deferral",
                                       round_=self.stats.steps,
                                       rid=req.rid, pages_needed=need)
        return False

    def _reserve_group(self, group: List[Tuple[int, Request]],
                       width: int):
        """Map page reservations for the lanes of one refill group (the
        admission guard already sized the round against the pool, so
        failure is a logic error, not a defer)."""
        for slot, req in group:
            if not self.allocator.reserve(
                    slot, self._reservation(width, req)):
                raise RuntimeError(
                    f"page reservation for slot {slot} failed after "
                    "admission passed the pool guard")
        self._sync_paged_stats()

    def _free_finished_lanes(self, sched: Scheduler):
        """Release finished lanes' pages before the scheduler clears
        their slots (the allocator is keyed by slot index).  In-flight
        ghost writes to a freed page are harmless: any future owner's
        first enqueued op rewrites every position it will ever read."""
        for i, r in enumerate(sched.slots):
            if r is not None and r.finish_t is not None:
                self.allocator.free_lane(i)

    def _sync_paged_stats(self):
        a = self.allocator
        self.stats.pages_peak = a.peak_in_use
        self.stats.prefix_hits = a.prefix_hits
        self.stats.prefix_tokens_saved = a.prefix_tokens_saved

    def _prefix_entries(self, group: List[Tuple[int, Request]],
                        rows: int, width: int):
        """Provenance keys for one refill group's shareable prompt
        prefixes: per row, the first m = (width - 1) // P pages.  The
        page holding the final draft pair is lane-divergent past the
        prompt (the first sampled token lands there), so it never
        shares.  Keys are built host-side from the request prompts —
        no device sync."""
        if self.allocator is None or not self.allocator.share_prefix:
            return []
        m = (width - 1) // self.page_size
        if m <= 0:
            return []
        entries = []
        for slot, req in group:
            pad = width - len(req.prompt)
            toks = [0] * pad + list(req.prompt)
            key = self.allocator.prefix_key(rows, width, pad, toks, m,
                                            salt=self._deploy_seq)
            entries.append((slot, key, m))
        return entries

    def _publish_prefixes(self, entries):
        """After a commit lands: register each row's prefix pages — or,
        when an identical prefix is already registered, adopt the shared
        pages and free the private duplicates.  The bytes are identical
        by provenance, so the enqueued commit's writes into the adopted
        range were harmless rewrites of the shared pages' own bytes'
        twins; nothing re-reads the freed privates (the repointed table
        ships before the next table-consuming dispatch)."""
        for slot, key, m in entries:
            hit = self.allocator.lookup(key)
            if hit is not None:
                self.allocator.adopt(slot, hit[:m])
            else:
                self.allocator.publish(key, slot, m)
        if entries:
            self._sync_paged_stats()

    def _try_adopt(self, pl: _ChunkPipeline,
                   group: List[Tuple[int, Request]]):
        """Prefix-registry probe at pipeline spawn: when every row's
        provenance key hits, the pipeline skips the prefill chunks the
        shared pages cover — ``_resume_pipeline`` seeds its staging from
        those pages (zero prefill row-tokens, the measured saving) and
        chunking resumes at the next chunk boundary.  The lane keeps its
        own page reservation; page-level dedup happens at commit
        (``_publish_prefixes``), so mid-pipeline decode ghost-writes can
        never land in shared pages."""
        pl.deploy_seq = self._deploy_seq
        pl.pub_entries = self._prefix_entries(group, pl.rows, pl.width)
        if self._chunk_resume_fn is None or not pl.pub_entries:
            return
        m = (pl.width - 1) // self.page_size
        hits = []
        for _, key, _ in pl.pub_entries:
            hit = self.allocator.lookup(key)
            if hit is None:
                return
            hits.append(hit[:m])
        # largest chunk boundary the shared pages fully cover (strictly
        # inside the prompt, so at least one chunk always remains to
        # regenerate the pipeline's logits/last-capture columns)
        q, b = 0, pl.first_width
        while b < pl.width:
            if b <= m * self.page_size:
                q = b
            b += pl.chunk
        if q <= 0:
            return
        mq = -(-q // self.page_size)
        rows = [h[:mq] for h in hits]
        rows += [rows[0]] * (pl.rows - len(rows))   # pow2 padding rows
        pl.resume_q = q
        pl.resume_rows = np.asarray(rows, np.int32)
        self.allocator.prefix_hits += len(group)
        self.allocator.prefix_tokens_saved += len(group) * q
        self._sync_paged_stats()

    def _resume_pipeline(self, pl: _ChunkPipeline, cache, dcache):
        """Dispatch the staging-seed op for a spawn-time registry hit:
        positions [0, resume_q) come from shared pages instead of
        prefill chunks.  Dispatched in the same host gap as the spawn,
        so no later-enqueued op can have rewritten the donor pages (XLA
        executes enqueue-order; page frees only reach the device through
        ops enqueued afterwards)."""
        pl.cache, pl.dcache = self._chunk_resume_fn(
            pl.width, pl.resume_q, cache, dcache,
            jnp.asarray(pl.resume_rows), pl.pad)
        pl.pos = pl.resume_q
        pl.resume_q = 0

    def _publish_pipeline(self, pl: _ChunkPipeline):
        """Commit-time publish/dedup for one pipeline — skipped when a
        draft deploy landed mid-pipeline (its draft pages then mix two
        drafts' bytes and match no clean provenance key)."""
        if self.allocator is None:
            return
        if self._deploy_seq != pl.deploy_seq:
            return
        self._publish_prefixes(pl.pub_entries)

    def release_prefix_cache(self):
        """Drop the shared-prefix registry (drain hygiene / leak
        checks).  No-op on dense engines."""
        if self.allocator is not None:
            self.allocator.release_prefix_cache()

    # ------------------------------------------- chunked refill pipeline
    def _note_prefill_op(self, rows: int, width: int):
        """Record one prefill dispatch (one-shot refill, prologue, or
        pipeline chunk) in the deterministic stall metrics."""
        self.stats.prefill_op_width.add(width)
        self.stats.prefill_row_tokens += rows * width

    def _make_pipeline(self, admitted, cohort: int = 0,
                       order: int = 0) -> _ChunkPipeline:
        return _ChunkPipeline(admitted, self._refill_arrays(admitted),
                              self.prefill_chunk, cohort, order)

    def _spawn_pipelines(self, admitted):
        """One chunk pipeline per refill group of the admission batch
        (group formation delegated to the ``CommitPolicy`` — per
        padded-width bucket by default) — several refills' chunks then
        pipeline through the same inter-superstep gaps.  The groups
        share a commit cohort (see ``_ChunkPipeline``)."""
        cohort = self._cohort_next
        self._cohort_next += 1
        for i, group in enumerate(self.policy.commit.refill_groups(
                admitted, self.prefill_chunk)):
            pl = self._make_pipeline(group, cohort, i)
            if self.paged:
                self._reserve_group(group, pl.width)
                self._try_adopt(pl, group)
            self._pipelines.append(pl)

    def _chunk_args(self, pl: _ChunkPipeline):
        """Host-side slices for the pipeline's next chunk: (width,
        chunk tokens, lookahead-shifted draft-pair tokens, advance)."""
        w = pl.width_at(pl.pos)
        a, b = pl.pos, pl.pos + w
        toks_c = pl.toks[:, a:b]
        # draft pairs are (capture i, token i+1): lookahead-shifted token
        # columns, sliced host-side from the full prompt; the final pair
        # width - 1 does not exist, so the last chunk ingests one fewer
        adv = min(w, pl.width - 1 - a)
        nxt = pl.toks[:, a + 1:b + 1]
        if nxt.shape[1] < w:
            nxt = jnp.pad(nxt, ((0, 0), (0, w - nxt.shape[1])))
        return w, toks_c, nxt, jnp.full((pl.rows,), adv, jnp.int32)

    def _advance_pipeline(self, pl: _ChunkPipeline) -> int:
        """Dispatch the next chunk of one pipeline (enqueued behind the
        in-flight superstep, like every refill op).  Returns the op's
        row-token cost."""
        w, toks_c, nxt, adv_j = self._chunk_args(pl)
        with self.tracer.span("prefill.chunk", rows=pl.rows, width=w):
            if pl.pos == 0:
                pl.cache, pl.dcache, pl.logits, pl.caps_last = \
                    self._chunk_start_fn(pl.width, self.params,
                                         self.dparams, toks_c, nxt,
                                         pl.pad, adv_j)
            else:
                pl.cache, pl.dcache, pl.logits, pl.caps_last = \
                    self._chunk_cont_fn(self.params, self.dparams,
                                        pl.cache, pl.dcache, toks_c,
                                        nxt, adv_j)
        pl.pos += w
        self.stats.prefill_chunks += 1
        self._note_prefill_op(pl.rows, w)
        self._obs_chunk(pl, w)
        return pl.rows * w

    def _obs_chunk(self, pl: _ChunkPipeline, w: int):
        """Flight-recorder note for one dispatched prefill chunk (every
        member request of the pipeline advanced by ``w`` columns)."""
        if self.recorder.enabled:
            for _, req in pl.admitted:
                self.recorder.note(req.rid, "prefill_chunk",
                                   round_=self.stats.steps,
                                   pos=pl.pos, width=w)

    def _plan_gap(self, cache, dcache) -> List[_ChunkPipeline]:
        """This gap's chunk dispatches, in order (a pipeline appears
        once per chunk), under the per-gap prefill budget of
        ``batch x prefill_chunk`` row-tokens (pad rows counted, as
        ``prefill_row_tokens`` counts them).

        Every live pipeline first advances one chunk, so none starves.
        The budget left over then goes chunk by chunk to the pipeline
        with the most prompt columns left, so the members of a cohort
        finish in the same gap, until the next chunk would pass the
        budget.  When the live pipelines' rows fill the budget this is
        one chunk per pipeline.  Spawn-time prefix resumes are seeded
        first (they move a pipeline's start column)."""
        live = [pl for pl in self._pipelines if not pl.ready]
        for pl in live:
            if pl.resume_q and pl.pos == 0:
                self._resume_pipeline(pl, cache, dcache)
        plan = list(live)                      # the one-chunk floor
        spent = sum(pl.rows * pl.width_at(pl.pos) for pl in live)
        # prompt columns each pipeline has left after the floor; every
        # further chunk is exactly prefill_chunk wide
        left = [pl.width - pl.pos - pl.width_at(pl.pos) for pl in live]
        while live:
            i = max(range(len(live)), key=left.__getitem__)
            cost = live[i].rows * self.prefill_chunk
            if left[i] <= 0 or spent + cost > self._gap_budget:
                break
            spent += cost
            left[i] -= self.prefill_chunk
            plan.append(live[i])
        return plan

    def _advance_pipelines_ss(self, cache, dcache, state, max_new,
                              pending):
        """Advance the in-flight pipelines by one gap's chunk plan
        (``_plan_gap``), with cohort-synchronized commits.

        Pass 1 — chunks: each planned chunk is dispatched in plan
        order.  A pipeline that is the *only* member of its cohort runs
        its final chunk fused with the commit (one dispatch, like a
        one-shot refill); a pipeline with cohort siblings runs its
        final chunk unfused and waits (``ready``).

        Pass 2 — cohorts: a cohort whose members are all staged commits
        them in admission order, in one gap, so the lanes of one
        admission batch activate together — the round-density property
        a one-shot refill op gets for free.

        Committed first tokens ride the pending telemetry record — zero
        extra host syncs; with no record in flight they are committed
        immediately (stream prologue).  Returns the updated live state
        plus (row-token cost, committed-pipeline count)."""
        gap_tokens = 0
        commits = 0
        committed = []
        cache, dcache = self._ship_tables(cache, dcache)

        def _emit_first(fdev, pl):
            if pending is not None:
                pending["refills"].append((fdev, pl.admitted))
            else:
                self._commit_firsts(fdev, pl.admitted)

        for pl in self._plan_gap(cache, dcache):
            if pl.pos + pl.width_at(pl.pos) < pl.width:   # interior chunk
                gap_tokens += self._advance_pipeline(pl)
                continue
            # commit policy: eager pipelines always commit alone (fused
            # final chunk, the moment prefill completes); cohort
            # pipelines wait for their admission-batch siblings
            solo = (not self.policy.commit.cohort
                    or not any(q.cohort == pl.cohort and q is not pl
                               for q in self._pipelines))
            if not solo:
                # final chunk, cohort siblings still prefilling: stage
                # and wait (commit lands with the cohort in pass 2)
                gap_tokens += self._advance_pipeline(pl)
                pl.ready = True
                continue
            w, toks_c, nxt, adv_j = self._chunk_args(pl)
            with self.tracer.span("prefill.chunk", rows=pl.rows,
                                  width=w, fused_commit=True):
                if pl.pos == 0:
                    cache, dcache, state, max_new, fdev = \
                        self._chunk_final_start_fn(
                            pl.width, self.params, self.dparams, toks_c,
                            nxt, pl.pad, adv_j, cache, dcache, state,
                            max_new, pl.mask, pl.src, pl.budgets, pl.sids)
                else:
                    cache, dcache, state, max_new, fdev = \
                        self._chunk_final_cont_fn(
                            self.params, self.dparams, pl.cache, pl.dcache,
                            toks_c, nxt, adv_j, cache, dcache, state,
                            max_new, pl.mask, pl.src, pl.budgets, pl.sids)
            pl.pos += w
            self.stats.prefill_chunks += 1
            self._note_prefill_op(pl.rows, w)
            self._obs_chunk(pl, w)
            gap_tokens += pl.rows * w
            self.stats.refills += len(pl.admitted)
            commits += 1
            committed.append(pl)
            _emit_first(fdev, pl)
            self._publish_pipeline(pl)

        cohorts = {}
        for pl in self._pipelines:
            if pl not in committed:
                cohorts.setdefault(pl.cohort, []).append(pl)
        for members in cohorts.values():
            if not all(q.ready for q in members):
                continue
            for q in sorted(members, key=lambda q: q.order):
                with self.tracer.span("prefill.commit", rows=q.rows):
                    cache, dcache, state, max_new, fdev = \
                        self._chunk_commit_ss_fn(
                            self.params, self.dparams, cache, dcache,
                            state, max_new, q.cache, q.dcache, q.logits,
                            q.caps_last, q.mask, q.src, q.budgets, q.sids)
                self.stats.refills += len(q.admitted)
                commits += 1
                committed.append(q)
                _emit_first(fdev, q)
                self._publish_pipeline(q)
        self._pipelines = [pl for pl in self._pipelines
                           if pl not in committed]
        return cache, dcache, state, max_new, gap_tokens, commits

    def _advance_pipelines_step(self, cache, dcache, carry, active, sids,
                                steps):
        """Pipeline advance for the per-step reference loop, by the same
        per-gap chunk plan as the superstep loop (``_plan_gap``):
        commits scatter the staging lanes into the live carry and update
        the host lane masks in place (no telemetry pipelining here)."""
        gap_tokens = 0
        committed = []
        cache, dcache = self._ship_tables(cache, dcache)
        for pl in self._plan_gap(cache, dcache):
            gap_tokens += self._advance_pipeline(pl)
            if not pl.done:
                continue
            committed.append(pl)
            cache, dcache, carry, fdev = self._chunk_commit_step_fn(
                self.params, self.dparams, cache, dcache, carry,
                pl.cache, pl.dcache, pl.logits, pl.caps_last, pl.mask,
                pl.src, pl.sids)
            self.stats.refills += len(pl.admitted)
            self._publish_pipeline(pl)
            first_np = np.asarray(fdev)
            for row, (slot, req) in enumerate(pl.admitted):
                self._commit_first(req, int(first_np[row]))
                active[slot] = req.finish_t is None
                sids[slot] = req.sid
                steps[slot] = 1
        self._pipelines = [pl for pl in self._pipelines
                           if pl not in committed]
        return cache, dcache, carry, gap_tokens

    def _empty_state(self):
        """All-inert device serving state for a chunked-prefill stream
        start: zero caches and a unit carry.  Every lane stays inactive
        (skipped by the superstep's outer cond, masked in the stepwise
        loop) until its pipeline's commit writes real state."""
        b = self.batch
        cache = T.init_cache(self.cfg, b, self.max_len,
                             page_size=self.page_size,
                             num_pages=self.num_pages)
        dcache = eagle.init_draft_cache(self.dcfg, b, self.max_len,
                                        page_size=self.page_size,
                                        num_pages=self.num_pages)
        carry = spec.SpecCarry(
            feats=jnp.zeros((b, self.gamma + 1, 3 * self.cfg.d_model),
                            self.cfg.act_dtype),
            tokens=jnp.zeros((b, self.gamma + 1), jnp.int32),
            advance=jnp.ones((b,), jnp.int32))
        first = jnp.zeros((b,), jnp.int32)
        return cache, dcache, carry, first

    # ----------------------------------------------- superstep hot path
    @staticmethod
    def _materialize(prev):
        """Pull telemetry to host; the bulky packed signal buffers stay
        device-side and are fetched lazily in ``_unpack_superstep`` only
        if the controller actually has collection enabled."""
        return {k: v if k.startswith("sig_") else np.asarray(v)
                for k, v in prev.items()}

    def _stream_superstep(self, sched, reqs0, cache, dcache, carry, first,
                          t0, on_complete, cold=False):
        with self.tracer.span("serve.start"):
            if cold:
                # chunked-prefill start: every lane is inert (budgets
                # and activity land with its pipeline's commit)
                max_new = jnp.zeros((self.batch,), jnp.int32)
                active0 = jnp.zeros((self.batch,), bool)
            else:
                max_new = jnp.asarray([r.max_new_tokens for r in reqs0],
                                      jnp.int32)
                active0 = jnp.asarray([r.finish_t is None for r in reqs0],
                                      bool)
            state = spec.init_superstep_state(
                carry, first, self._base_key, accept_ema=self.accept_ema,
                eos_id=self.eos_id, active0=active0,
                sids=self._slot_sids(reqs0),
                capture_window=self.reseed_window)
        if cold and self._pipelines:
            # initial pipelines take the prologue's slot in the dispatch
            # order.  No lane is decoding yet, so there is nothing to
            # interleave with — run chunks back-to-back until the first
            # commit activates lanes (the stall bound only constrains
            # gaps where residents decode)
            gap = 0
            commits = 0
            chunks0 = self.stats.prefill_chunks
            with self.tracer.span("prefill.advance") as sp:
                while self._pipelines and commits == 0:
                    cache, dcache, state, max_new, g, commits = \
                        self._advance_pipelines_ss(cache, dcache, state,
                                                   max_new, None)
                    # each back-to-back plan is a gap of its own in the
                    # stall metrics: no lane decodes between them
                    self.stats.prefill_gap_tokens.add(g)
                    gap += g
                sp.set(chunks=self.stats.prefill_chunks - chunks0,
                       row_tokens=gap)
        # one-superstep double buffer: superstep t+1 is dispatched before
        # t's telemetry is pulled, so the D2H sync overlaps device
        # compute; refills scheduled after draining t are enqueued behind
        # t+1 and take effect in t+2, their first tokens riding along
        # with t's... drained record ("refill" attachment below)
        pending = None
        stall = 0
        while True:
            # zero-sync deploy pickup: one host attribute read; on a new
            # version the swap is a reference rebind and the optional
            # re-seed is one enqueued device op (no telemetry pull)
            ver = self._poll_deploy()
            if ver is not None and self._reseed_fn is not None:
                with self.tracer.span("reseed", seq=ver.seq):
                    dcache = self._reseed_fn(self.dparams, dcache, state)
                self.stats.reseeds += 1
            dispatched = False
            if sched.has_work():
                with self.tracer.span("superstep.dispatch",
                                      rounds=self.superstep_rounds):
                    cache, dcache = self._ship_tables(cache, dcache)
                    out = self._superstep_fn(
                        self.params, self.dparams, cache, dcache, state,
                        max_new, self.policy.speculation.dispatch_table())
                self.stats.dispatches += 1
                cache, dcache, state = (out["cache"], out["dcache"],
                                        out["state"])
                prev, pending = pending, {"rounds": out["rounds"],
                                          "slots": list(sched.slots),
                                          "n_prefill": sum(
                                              len(p.admitted)
                                              for p in self._pipelines),
                                          "refills": []}
                dispatched = True
            else:
                prev, pending = pending, None
            if prev is None:
                if not dispatched:
                    wait = sched.next_arrival_in()
                    if wait is None and not sched.more_coming():
                        if self._spills:
                            # unreachable by construction: every free
                            # slot is offered to the spill store at each
                            # boundary before the loop can go idle
                            raise RuntimeError(
                                f"{len(self._spills)} spilled requests "
                                "were never restored")
                        break
                    # gated-arrival gap: no dispatch, yield to the
                    # trainer; admission resumes via the normal
                    # drain-then-refill path once the head arrives
                    self._idle_tick(wait)
                continue
            with self.tracer.span("superstep.unpack"):
                progressed = self._drain(prev, t0)
            n_restores0 = self.stats.restores
            with self.tracer.span("serve.boundary"):
                cache, dcache, state, max_new, admitted = \
                    self._overload_boundary(sched, on_complete, cache,
                                            dcache, state, max_new)
            gap_tokens = 0
            if admitted and not self.prefill_chunk:
                with self.tracer.span("refill", rows=len(admitted)):
                    cache, dcache, state, max_new, gap = \
                        self._refill_one_shot(admitted, cache, dcache,
                                              state, max_new, pending)
                self.stats.prefill_gap_tokens.add(gap)
                gap_tokens += gap
            elif admitted or self._pipelines:
                chunks0 = self.stats.prefill_chunks
                with self.tracer.span("prefill.advance") as sp:
                    if admitted:
                        # chunked: new pipelines; their first chunks
                        # dispatch in the advance below, in the refill
                        # op's dispatch slot
                        self._spawn_pipelines(admitted)
                    cache, dcache, state, max_new, gap, commits = \
                        self._advance_pipelines_ss(cache, dcache, state,
                                                   max_new, pending)
                    self.stats.prefill_gap_tokens.add(gap)
                    gap_tokens += gap
                    # the drained superstep was empty (no resident lane
                    # decoding): nothing to interleave with, so run the
                    # pipelines straight to the next commit instead of
                    # trickling one plan per empty dispatch (each plan
                    # still a gap of its own in the stall metrics)
                    while (self._pipelines and not progressed
                           and commits == 0):
                        cache, dcache, state, max_new, gap, commits = \
                            self._advance_pipelines_ss(
                                cache, dcache, state, max_new, pending)
                        self.stats.prefill_gap_tokens.add(gap)
                        gap_tokens += gap
                    sp.set(chunks=self.stats.prefill_chunks - chunks0,
                           row_tokens=gap_tokens)
            # defensive stall guard: every drained superstep must either
            # commit rounds, retire requests, admit new ones, or move a
            # chunk pipeline forward
            stall = 0 if (progressed or admitted or gap_tokens
                          or self.stats.restores > n_restores0) \
                else stall + 1
            if stall > 4:
                raise RuntimeError(
                    "serve_stream made no progress over 5 supersteps "
                    "(device/host slot state diverged)")

    def _refill_one_shot(self, admitted, cache, dcache, state, max_new,
                         pending):
        """One-shot refill of the admitted requests' lanes, enqueued
        behind the in-flight superstep.  Their first tokens ride the
        pending telemetry record (zero extra host syncs), or are
        committed now when none is in flight.  Returns the updated
        device bindings and the op's row-token cost."""
        args = self._refill_arrays(admitted)
        rows, width = int(args[0].shape[0]), int(args[0].shape[1])
        self._note_prefill_op(rows, width)
        if self.paged:
            self._reserve_group(admitted, width)
            cache, dcache = self._ship_tables(cache, dcache)
        cache, dcache, state, max_new, fdev = self._refill_ss_fn(
            self.params, self.dparams, cache, dcache, state, max_new,
            *args)
        self.stats.refills += len(admitted)
        if self.paged:
            self._publish_prefixes(self._prefix_entries(admitted, rows,
                                                        width))
        if pending is not None:
            pending["refills"].append((fdev, admitted))
        else:
            self._commit_firsts(fdev, admitted)
        return cache, dcache, state, max_new, rows * width

    def _drain(self, rec, t0) -> bool:
        """Unpack one in-flight superstep record: replay its telemetry,
        then commit the first tokens of any refill (one-shot or chunk-
        pipeline commit) that was enqueued behind it.  Returns True if
        any round was valid (progress)."""
        with self.tracer.span("superstep.wait"):
            ys = self._materialize(rec["rounds"])
        rids = [r.rid if r is not None else -1 for r in rec["slots"]]
        progressed = self._unpack_superstep(ys, rec["slots"], rids, t0,
                                            n_prefill=rec.get("n_prefill",
                                                              0))
        for fdev, admitted in rec["refills"]:
            self._commit_firsts(fdev, admitted)
        return progressed

    def _unpack_superstep(self, ys, requests, rids, t0,
                          n_prefill: int = 0) -> bool:
        """Replay one superstep's host-side bookkeeping from device
        telemetry: token commit, stats/timeline, Algorithm 1 controller
        and packed-signal ingestion.  ``requests`` is the per-slot
        residency snapshot taken at dispatch (None = free lane);
        ``n_prefill`` the number of lanes that were mid-chunk-prefill at
        dispatch (inert for decode, tracked separately for occupancy).
        Returns True if any round was valid (i.e. the superstep did
        work; False means every lane was already done at entry)."""
        valid = ys["valid"]
        sig_np = None            # lazily-fetched packed signal buffers
        any_valid = False
        rec_on = self.recorder.enabled
        # requests that received tokens, for the delivery instant
        got = set() if self.tracer.enabled else None
        for r in range(valid.shape[0]):
            if not valid[r]:
                break
            any_valid = True
            use_spec = bool(ys["use_spec"][r])
            ell = float(ys["ell"][r])
            alpha = float(ys["alpha"][r])
            n_eff = ys["n_eff"][r]
            toks = ys["tokens"][r]
            active_after = ys["active_after"][r]
            for i, req in enumerate(requests):
                if req is None:
                    continue
                n = int(n_eff[i])
                if n:
                    req.generated.extend(int(t) for t in toks[i, :n])
                    if got is not None:
                        got.add(req.rid)
                    if rec_on:
                        self.recorder.note(req.rid, "commit",
                                           round_=self.stats.steps,
                                           n=n, spec=use_spec)
                # a lane is inactive-but-unfinished while its chunk
                # pipeline is still prefilling (first_token_t unset);
                # only requests that actually started emitting may be
                # retired by decode telemetry
                if (not active_after[i] and req.finish_t is None
                        and req.first_token_t is not None):
                    self._finish(req)
            busy = int((n_eff > 0).sum())
            self.stats.tokens_out += int(n_eff.sum())
            self.stats.steps += 1
            self.stats.spec_steps += int(use_spec)
            self.stats.accept_len_sum += ell
            self.stats.accept_len_n += 1
            self.stats.lane_rounds += len(requests)
            self.stats.busy_lane_rounds += busy
            self.stats.prefill_lane_rounds += n_prefill
            self.accept_ema = float(ys["ema"][r])
            if self.drafter is not None:
                self.drafter.enabled = use_spec
            # park/resume control: host-side, from the same telemetry
            # replay (one superstep of pipelining lag, zero syncs)
            self.policy.speculation.observe_round(
                int(active_after.sum()), self.accept_ema, use_spec)
            decision = Decision.NONE
            if self.controller is not None:
                decision = self.controller.observe_gated(
                    alpha, int(ys["n_sig"][r]))
                if self.extractor is not None:
                    self.extractor.enabled = \
                        self.controller.collection_enabled
            self._apply_capture_park()
            if (self.extractor is not None and self.extractor.enabled
                    and "sig_feats" in ys):
                if sig_np is None:
                    with self.tracer.span("signals.fetch"):
                        sig_np = tuple(np.asarray(ys[k]) for k in
                                       ("sig_feats", "sig_tokens",
                                        "sig_counts"))
                self.extractor.ingest_packed(
                    rids, sig_np[0][r], sig_np[1][r], sig_np[2][r])
            self.stats.timeline.append({
                "t": self._clock() - t0, "spec": use_spec,
                "accept_len": ell, "alpha": alpha,
                "decision": decision.value, "busy_lanes": busy,
            })
        if got:
            self.tracer.instant("serve.deliver", rids=sorted(got))
        return any_valid

    # ------------------------------------------ per-step reference loop
    def _stream_stepwise(self, sched, cache, dcache, carry, t0,
                         on_complete, cold=False):
        b = self.batch
        slots = list(sched.slots)
        active = (np.zeros((b,), bool) if cold else
                  np.array([r is not None and r.finish_t is None
                            for r in slots], bool))
        # host-side twin of the superstep's (sid, step_idx) state: lane
        # keys are derived per step from the engine base key, so sampled
        # streams are per-request and scheduling-invariant
        sids = self._slot_sids(slots)
        steps = np.ones((b,), np.int32)
        if cold and self._pipelines:
            cache, dcache, carry, gap = self._advance_pipelines_step(
                cache, dcache, carry, active, sids, steps)
            if gap:
                self.stats.prefill_gap_tokens.add(gap)
        while True:
            self._poll_deploy()      # swap-only (no ring in this mode)
            admitted = self._retire_and_admit(sched, on_complete)
            if admitted and self.prefill_chunk:
                self._spawn_pipelines(admitted)
                slots = list(sched.slots)
            elif admitted:
                args = self._refill_arrays(admitted)
                self._note_prefill_op(args[0].shape[0], args[0].shape[1])
                self.stats.prefill_gap_tokens.add(
                    args[0].shape[0] * args[0].shape[1])
                if self.paged:
                    self._reserve_group(admitted, int(args[0].shape[1]))
                    cache, dcache = self._ship_tables(cache, dcache)
                with self.tracer.span("refill", rows=int(args[0].shape[0]),
                                      width=int(args[0].shape[1])):
                    cache, dcache, carry, fdev = self._refill_step_fn(
                        self.params, self.dparams, cache, dcache, carry,
                        args[0], args[1], args[2], args[3], args[5])
                self.stats.refills += len(admitted)
                if self.paged:
                    self._publish_prefixes(self._prefix_entries(
                        admitted, int(args[0].shape[0]),
                        int(args[0].shape[1])))
                first_np = np.asarray(fdev)
                for row, (slot, req) in enumerate(admitted):
                    self._commit_first(req, int(first_np[row]))
                    active[slot] = req.finish_t is None
                    sids[slot] = req.sid
                    steps[slot] = 1
                slots = list(sched.slots)
            if self._pipelines:
                cache, dcache, carry, gap = self._advance_pipelines_step(
                    cache, dcache, carry, active, sids, steps)
                if gap:
                    self.stats.prefill_gap_tokens.add(gap)
                slots = list(sched.slots)
            if not active.any():
                if sched.has_work():
                    continue     # residents all EOS'd at refill; admit more
                if sched.more_coming():
                    self._idle_tick(sched.next_arrival_in())
                    continue     # gated arrivals still due
                break
            # speculate-vs-plain: the SpeculationPolicy's host-side twin
            # of the in-graph gate (drafter.update when a drafter is
            # set; park/probe schedule when the park control is on)
            use_spec = self.policy.speculation.step_decision(
                int(active.sum()), self.accept_ema)
            self.stats.dispatches += 1
            cache, dcache = self._ship_tables(cache, dcache)
            keys = (self._null_keys if self.greedy else
                    self._lane_keys_fn(jnp.asarray(sids),
                                       jnp.asarray(steps)))
            steps = np.where(active, steps + 1, steps)
            if use_spec:
                with self.tracer.span("step.dispatch", spec=True):
                    out = self._spec_fn(self.params, self.dparams, cache,
                                        dcache, carry, keys)
                cache, dcache, carry = (out["cache"], out["dcache"],
                                        out["carry"])
                n_commit = np.asarray(out["n_commit"])
                toks_np = np.asarray(out["tokens"])
                # f32 arithmetic exactly as the fused superstep computes
                # in-graph, so the Eq. 5 threshold compare can never
                # straddle a rounding boundary between the two modes
                na = np.float32(active.sum())
                ell32 = np.float32(
                    np.float32(n_commit[active].sum()) / na)
                alpha = float(np.float32(
                    np.float32((n_commit[active] - 1).sum()) / na)
                    / np.float32(self.gamma))
                ell = float(ell32)
                self.accept_ema = float(
                    self._ema_fn(jnp.float32(self.accept_ema),
                                 jnp.float32(ell32)))
                self.stats.spec_steps += 1
            else:
                with self.tracer.span("step.dispatch", spec=False):
                    out = self._plain_fn(self.params, cache, carry, keys)
                cache, carry = out["cache"], out["carry"]
                n_commit = np.ones((b,), np.int32)
                toks_np = np.asarray(out["tokens"])
                alpha = 0.0
                ell = 1.0
            n_eff = np.zeros((b,), np.int32)
            eos_hit = np.zeros((b,), bool)
            for i, r in enumerate(slots):
                if r is None or not active[i]:
                    continue
                n = min(int(n_commit[i]),
                        max(r.max_new_tokens - len(r.generated), 0))
                if self.eos_id is not None:
                    eos_pos = np.flatnonzero(
                        toks_np[i, :n] == self.eos_id)
                    if eos_pos.size:
                        n = int(eos_pos[0]) + 1
                        eos_hit[i] = True
                n_eff[i] = n
            self.policy.speculation.observe_round(
                int(active.sum()), self.accept_ema, use_spec)
            self._apply_capture_park()
            if self.extractor is not None:
                # only tokens actually kept (post EOS/budget cut) become
                # training signals
                rids = [r.rid if r is not None else -1 for r in slots]
                mask = (np.arange(toks_np.shape[1])[None, :]
                        < n_eff[:, None])
                self.extractor.offer(rids, out["captures"], out["tokens"],
                                     jnp.asarray(mask))

            rec_on = self.recorder.enabled
            for i, r in enumerate(slots):
                if r is None or not active[i]:
                    continue
                r.generated.extend(int(t) for t in toks_np[i, :n_eff[i]])
                if rec_on and n_eff[i]:
                    self.recorder.note(r.rid, "commit",
                                       round_=self.stats.steps,
                                       n=int(n_eff[i]), spec=use_spec)
                if eos_hit[i] or r.done:
                    self._finish(r)
                    active[i] = False
            self.stats.tokens_out += int(n_eff.sum())
            self.stats.steps += 1
            self.stats.accept_len_sum += ell
            self.stats.accept_len_n += 1
            self.stats.lane_rounds += b
            self.stats.prefill_lane_rounds += sum(
                len(p.admitted) for p in self._pipelines)
            busy = int((n_eff > 0).sum())
            self.stats.busy_lane_rounds += busy
            n_sig = int(n_commit[active].sum()) if active.any() else 0
            decision = Decision.NONE
            if self.controller is not None:
                decision = self.controller.observe_gated(alpha, n_sig)
                if self.extractor is not None:
                    self.extractor.enabled = \
                        self.controller.collection_enabled
            self.stats.timeline.append({
                "t": self._clock() - t0, "spec": use_spec,
                "accept_len": ell, "alpha": alpha,
                "decision": decision.value, "busy_lanes": busy,
            })

    def _pick(self, logits, sids):
        if self.greedy:
            return logits.argmax(-1).astype(jnp.int32)
        return self._pick_sampled_fn(logits, jnp.asarray(sids, jnp.int32))
