"""TIDE system orchestrator (paper Fig. 1): wires the Inference Serving
Engine, Training Signal Extractor, Acceptance Length Monitor, Adaptive
Drafter, and Draft Model Training Engine into the full adaptive loop.

Decoupled architecture (paper §3.3/§5.5): serving and training are
separate engines joined by two one-way, never-blocking seams —

  * **signals out**: the engine's superstep unpack pushes packed
    hidden-state windows into a bounded drop-oldest
    ``core.transport.SignalChannel`` (backpressure drops oldest, never
    stalls serving);
  * **drafts in**: the ``training.service.TrainingService`` runs
    ``DraftTrainer.train_cycle`` off-path — on its own device/submesh
    when the host has one (``transport.pick_training_device``), else on
    a background thread whose jitted train steps release the GIL and
    fill superstep-boundary + arrival-gap slack — and publishes each
    gate-accepted draft as a versioned ``DraftVersion`` into a
    lock-free deploy slot that the engine polls once per superstep
    (zero extra host↔device syncs; resident lanes' draft caches are
    re-seeded in place from the rolling capture ring).

Two training modes: ``async_train=False`` (default) calls
``service.drain()`` at request-completion boundaries — blocking, fully
deterministic, byte-compatible with the legacy synchronous scheduler —
while ``async_train=True`` starts the background loop and serving never
waits on training.  Every control decision of the paper (Algorithm 1
collection gating, deploy-if-improved) is identical in both modes; the
asynchrony is an interface property.

Disaggregation (``TideConfig(fleet=FleetConfig(...))``, repro/fleet,
docs/disaggregation.md): with ``fleet.trainer_endpoint`` set, the same
two seams cross a *process* boundary — signals flow through a
``RemoteSignalChannel`` (identical bounded drop-oldest ring, drained
onto a socket off-path) to a ``TrainingService`` running in its own
process on its own XLA client (``repro.fleet.trainer_main``), and
published drafts come back as wire frames into the same lock-free
deploy slot the engine already polls.  Both training modes survive the
move: sync mode's ``drain()`` becomes a wire barrier whose ack is
ordered after every DRAFT frame it caused (byte-identical streams),
async mode stays zero-sync.  Trainer death degrades serving to the
last published draft (``summary()['trainer_failures']``), never a
hang.  ``fleet.replicas > 0`` scales out to a data-parallel engine
fleet behind a draft-version bus + front-end router — that topology
is served by ``repro.fleet.router.ServingFleet``; TideSystem itself
stays single-engine.

Serving control plane: all runtime scheduling decisions (admission
order, chunk-pipeline commit, the Eq. 5 speculate-vs-plain gate and
its park/resume control) are delegated to a composed
``serving.policy.ServingPolicy`` built from the unified
``ServingConfig`` — ``TideConfig(serving=ServingConfig(...))`` is the
one place to select FIFO/priority/EDF admission, cohort/eager commit,
speculation parking, chunked prefill, arrival gating, and the trainer
thread-contention cap (``trainer_threads``); the flat legacy
``TideConfig`` fields remain as a convenience/back-compat layer.

Memory scale: ``page_size``/``num_pages``/``share_prefix`` switch the
engine's per-lane dense KV caches to the paged memory model
(``core.paging``): fixed-size page pools behind per-lane block tables,
admission-time page reservations (slot count bounded by HBM actually
used, not ``batch x max_len``), and provenance-keyed copy-on-write
sharing of committed prompt-prefix pages across lanes — byte-identical
streams to dense serving, pinned in tests/test_paged.py.

Tree speculation: ``tree_width`` >= 1 swaps the linear gamma-chain
draft for a token tree — width top-k first continuations each extended
to a gamma-deep branch, flattened branch-major into one fixed
``width * gamma + 1``-row block (slot 0 = the committed token, branch
r's depth-j node at ``1 + r*gamma + (j-1)``) and verified in a single
tree-masked target pass.  The acceptance rule walks every branch and
commits the longest accepted root path; the commit compacts that
branch's K/V rows into the chain layout, so caches, telemetry, and
signal capture (accepted-path features only) keep their chain shapes
— the training loop and SignalStore semantics are unchanged.
``tree_width=1`` is bitwise identical to the chain engine
(tests/test_tree.py); the shape is carried by the SpeculationPolicy,
the seam a learned speculation controller would tune it through.

Observability: the system owns one ``repro.obs`` instrument set shared
by every component — a ``MetricsRegistry`` (``self.metrics``) whose
``serving.* / train.* / paging.* / spec.*`` namespaces are fed by the
engine's ServingStats, the training service/channel, the page
allocator, and the speculation policy (``summary()`` remains a thin
view over the same registry state); plus an optional span tracer and
per-request flight recorder built from ``TideConfig.obs``
(``ObsConfig``) and handed to the engine/service as collaborators.
All hooks are host-side at existing telemetry boundaries — superstep
unpack, admission, trainer publish, deploy poll — so observability-on
serving adds **zero** device syncs and observability-off is
byte-identical (nulls; gated in benchmarks/bench_hotloop.py).  See
docs/observability.md.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional

import jax

from repro.checkpoint.ckpt import DraftDeployGate
from repro.core import eagle
from repro.core.adaptive import AdaptiveDrafter, LatencyProfile
from repro.core.controller import TrainingController
from repro.core.signals import SignalExtractor
from repro.core.transport import SignalChannel, pick_training_device
from repro.fleet import FleetConfig
from repro.models.config import ModelConfig
from repro.obs import ObsConfig
from repro.obs.metrics import MetricsRegistry
from repro.serving.engine import ServingEngine
from repro.serving.policy import ServingConfig
from repro.serving.request import Request
from repro.training.draft_trainer import DraftTrainer
from repro.training.service import TrainingService


@dataclasses.dataclass
class TideConfig:
    """System configuration.  Serving knobs live in one unified
    ``serving.policy.ServingConfig`` (``TideConfig(serving=...)``); the
    flat legacy fields remain as a convenience layer — when ``serving``
    is omitted they assemble one, when it is given they mirror its
    values so legacy readers keep working."""
    gamma: int = 3
    batch_size: int = 4
    max_len: int = 160
    greedy: bool = True
    superstep_rounds: int = 8         # 0 = per-step reference loop
    eos_id: Optional[int] = None
    ema: float = 0.9                  # acceptance-EMA decay
    tree_width: int = 0               # >=1: draft token trees, verified
    #                                   in one tree-masked target pass
    #                                   (width=1 == chain, bitwise)
    adaptive_spec: bool = True        # False = TIDE-default (paper §5.4)
    selective_training: bool = True
    signal_window: int = 24
    n_threshold: int = 96             # samples per training cycle (tiny scale)
    train_epochs: int = 2
    train_min_steps: int = 80         # optimizer-step floor per cycle
    seed: int = 0
    # ---- decoupled-training subsystem
    async_train: bool = False         # background service vs drain-at-
    #                                   completion-boundaries (sync parity)
    channel_capacity: int = 512       # SignalChannel bound (batches)
    reseed_window: int = 0            # >0: re-seed resident draft caches
    #                                   on deploy from a W-pair ring
    gate_arrivals: bool = False       # respect trace arrival timestamps
    prefill_chunk: int = 0            # >0: chunked refill prefill (bound
    #                                   the long-prompt refill stall to
    #                                   batch x chunk row-tokens a gap);
    #                                   applies to waves and streams alike
    # ---- paged KV cache (core/paging.py; 0 = dense per-lane caches)
    page_size: int = 0                # >0: block-table page pools with
    #                                   admission-time reservations
    num_pages: int = 0                # pool size (0 = dense footprint)
    share_prefix: bool = True         # COW prompt-prefix sharing
    # ---- serving control plane (see serving/policy.py)
    admission: str = "fifo"           # fifo | priority | deadline (EDF)
    #                                   | wedf (priority-weighted EDF)
    commit: str = "cohort"            # cohort | eager chunk-pipeline commit
    admission_lookahead: int = 64     # reorder window (non-FIFO policies)
    # ---- overload resilience (docs/overload.md)
    preempt: str = "none"             # none | deadline: spill a loose
    #                                   resident lane when a tighter-
    #                                   deadline candidate is deferred
    shed: str = "none"                # none | expired | queue: drop
    #                                   hopeless queued requests
    shed_queue_depth: int = 64        # queue-shed depth bound
    idle_wait_s: float = 0.005        # gated-arrival idle-tick bound
    spec_park_patience: int = 0       # >0: park speculation + capture
    #                                   after N gated-off rounds
    spec_probe_interval: int = 8      # parked dispatches between probes
    trainer_threads: int = 0          # >0: pin/deprioritize the trainer
    #                                   client's host threads
    # ---- observability (repro/obs; host-side only, zero device syncs).
    #      Not a ServingConfig knob: the engine takes the built
    #      tracer/recorder as collaborators, never a config field.
    obs: Optional[ObsConfig] = None
    # ---- disaggregation (repro/fleet; docs/disaggregation.md).
    #      fleet.trainer_endpoint moves the TrainingService out of
    #      process over the fleet wire protocol (TideSystem handles
    #      this transparently: same sync/async modes, same summary);
    #      fleet.replicas > 0 selects the data-parallel replica fleet,
    #      served through repro.fleet.router.ServingFleet (TideSystem
    #      itself stays single-engine).
    fleet: Optional[FleetConfig] = None
    serving: Optional[ServingConfig] = None

    # knobs shared (by name) with ServingConfig: assembled into one
    # when ``serving`` is omitted, mirrored back when it is given — one
    # list, so a knob added to either side cannot silently desync
    # (tests/test_config_mirror.py asserts the list covers every
    # ServingConfig field)
    _SHARED_FIELDS = ("gamma", "batch_size", "max_len", "greedy", "seed",
                      "superstep_rounds", "eos_id", "ema", "tree_width",
                      "gate_arrivals", "prefill_chunk", "reseed_window",
                      "page_size", "num_pages", "share_prefix",
                      "admission", "commit", "admission_lookahead",
                      "preempt", "shed", "shed_queue_depth",
                      "idle_wait_s", "spec_park_patience",
                      "spec_probe_interval", "trainer_threads")

    def __post_init__(self):
        if self.serving is None:
            self.serving = ServingConfig(**{
                f: getattr(self, f) for f in self._SHARED_FIELDS})
            return
        # Flat fields explicitly set away from their TideConfig default
        # override the serving config; the rest become read mirrors of
        # it.  Because a constructed TideConfig's flat fields equal its
        # serving values (mirrored below), this also makes
        # ``dataclasses.replace(tc, batch_size=8)`` behave: the changed
        # field differs from both default and serving -> override; the
        # untouched ones equal serving -> no-op mirror.
        defaults = {f.name: f.default
                    for f in dataclasses.fields(type(self))}
        over = {f: getattr(self, f) for f in self._SHARED_FIELDS
                if getattr(self, f) != defaults[f]
                and getattr(self, f) != getattr(self.serving, f)}
        if over:
            self.serving = dataclasses.replace(self.serving, **over)
        for f in self._SHARED_FIELDS:
            setattr(self, f, getattr(self.serving, f))


class TideSystem:
    def __init__(self, cfg: ModelConfig, params, tide_cfg: TideConfig,
                 profile: Optional[LatencyProfile] = None,
                 dparams=None):
        self.cfg = cfg
        self.tcfg = tide_cfg
        self.dcfg = eagle.draft_config(cfg)
        if dparams is None:
            dparams = eagle.draft_init(self.dcfg,
                                       jax.random.key(tide_cfg.seed + 7))
        # the initial draft as the deploy gate holds it (on the trainer's
        # device, if it has one), and the device the engine's copy of it
        # is made again on at a reset (None: the same arrays)
        self._dparams0 = dparams
        self._serve_device = None
        # one shared instrument set for every component (see module
        # docstring, "Observability"); tracer/recorder default to the
        # null singletons when TideConfig.obs is unset
        self.obs = tide_cfg.obs if tide_cfg.obs is not None else ObsConfig()
        self.metrics = MetricsRegistry()
        self.tracer, self.recorder = self.obs.build()
        self.controller = TrainingController(
            n_threshold=tide_cfg.n_threshold * tide_cfg.signal_window,
            n_init=4)
        drafter = None
        if tide_cfg.adaptive_spec and profile is not None:
            drafter = AdaptiveDrafter(profile, gamma=tide_cfg.gamma)
        # --- training stack: in-process (thread / submesh) or
        # out-of-process over the fleet wire (docs/disaggregation.md).
        # Both expose the same poll/drain/reset/close surface, so every
        # serving-side mode below is transport-agnostic.
        remote = (tide_cfg.fleet is not None
                  and tide_cfg.fleet.trainer_endpoint is not None)
        if remote:
            from repro.fleet.remote import RemoteTrainingService
            self.service = RemoteTrainingService(
                tide_cfg.fleet.trainer_endpoint,
                tcfg=cfg, dcfg=self.dcfg,
                embed_params=params["embed"], dparams0=dparams,
                n_threshold=tide_cfg.n_threshold * tide_cfg.signal_window,
                signal_window=tide_cfg.signal_window,
                train_epochs=tide_cfg.train_epochs,
                train_min_steps=tide_cfg.train_min_steps,
                seed=tide_cfg.seed, async_train=tide_cfg.async_train,
                channel_capacity=max(tide_cfg.channel_capacity,
                                     tide_cfg.n_threshold),
                controller=self.controller,
                selective=tide_cfg.selective_training,
                engine_steps_fn=lambda: self.engine.stats.steps,
                tracer=self.tracer, registry=self.metrics)
            self.channel = self.service.channel
            self.trainer = None        # lives in the trainer process
            self.gate = self.service.gate   # serving-side version mirror
        else:
            train_device = (pick_training_device()
                            if tide_cfg.async_train else None)
            serve_device = (jax.devices()[0]
                            if train_device is not None else None)
            # the channel must be able to buffer at least one cycle's
            # worth of windows or training starves behind the
            # drop-oldest bound
            self.channel = SignalChannel(
                capacity=max(tide_cfg.channel_capacity,
                             tide_cfg.n_threshold),
                device=train_device, tracer=self.tracer)
            # the trainer reads only the frozen embeddings: keep a copy
            # on its own device, next to the drafts it trains; the gate
            # holds the trainer's drafts there too, and the engine its
            # own copy on the serving device, which no reference here
            # pins: the serving chip holds at most the draft it serves
            # and the one a deploy publishes
            embed = params["embed"]
            if train_device is not None:
                embed, self._dparams0 = jax.device_put(
                    (embed, dparams), train_device)
                self._serve_device = serve_device
            self.trainer = DraftTrainer(cfg, self.dcfg, embed,
                                        device=train_device)
            self.gate = DraftDeployGate(self._dparams0)
            self.service = TrainingService(
                self.trainer, self.gate, self.channel,
                controller=self.controller,
                selective=tide_cfg.selective_training,
                n_threshold=tide_cfg.n_threshold * tide_cfg.signal_window,
                signal_window=tide_cfg.signal_window,
                train_epochs=tide_cfg.train_epochs,
                train_min_steps=tide_cfg.train_min_steps,
                seed=tide_cfg.seed, publish_device=serve_device,
                trainer_threads=tide_cfg.trainer_threads,
                engine_steps_fn=lambda: self.engine.stats.steps,
                tracer=self.tracer, registry=self.metrics)
        self.store = self.channel     # back-compat alias (shared storage)
        self.extractor = SignalExtractor(self.channel,
                                         window=tide_cfg.signal_window,
                                         tracer=self.tracer)
        self.events = self.service.events
        # the engine consumes one unified ServingConfig + the composed
        # ServingPolicy it names (re-seed only makes sense with the
        # async deploy path, so sync mode zeroes it)
        scfg = dataclasses.replace(
            tide_cfg.serving,
            reseed_window=(tide_cfg.reseed_window if tide_cfg.async_train
                           else 0))
        self.engine = ServingEngine(
            cfg, params, self.dcfg, dparams, config=scfg,
            policy=scfg.make_policy(drafter),
            controller=self.controller if tide_cfg.selective_training
            else None,
            extractor=self.extractor,
            deploy_source=(self.service.poll if tide_cfg.async_train
                           else None),
            tracer=self.tracer, recorder=self.recorder,
            metrics=self.metrics)
        # start in collection mode so the cold draft trains immediately
        self.controller.collection_enabled = True
        if tide_cfg.async_train:
            self.service.start()

    # ----------------------------------------------------------- training
    def _drain_train(self, _req=None):
        """Synchronous parity mode: run every cycle the buffered signals
        allow, blocking serving (the legacy training schedule), then
        deploy immediately so the next dispatch uses the new draft
        (same pickup protocol as the async per-superstep poll)."""
        self.service.drain()
        self.engine._poll_deploy(self.service.poll)

    # ------------------------------------------------------------ serving
    def run(self, waves: Iterable[List], max_new_tokens: int = 48
            ) -> List[Request]:
        """Serve a workload stream (already grouped into waves of
        (domain, prompt) pairs). Returns all completed requests."""
        done: List[Request] = []
        sync = not self.tcfg.async_train
        for wave in waves:
            reqs = [Request(prompt=p, domain=d,
                            max_new_tokens=max_new_tokens)
                    for d, p in wave]
            self.engine.serve_wave(reqs)
            done.extend(reqs)
            if sync:
                self._drain_train()
        return done

    def run_stream(self, requests: Iterable[Request]) -> List[Request]:
        """Serve a request stream with continuous batching.  In sync
        mode the training service is drained at request-completion
        boundaries (blocking, deterministic — a passing draft hot-swaps
        in mid-stream exactly as the legacy scheduler did); in async
        mode serving never waits — the service trains in the
        background and the engine picks deploys up from the lock-free
        slot once per superstep."""
        on_complete = (self._drain_train if not self.tcfg.async_train
                       else None)
        return self.engine.serve_stream(requests, on_complete=on_complete)

    def requests_from_trace(self, trace) -> List[Request]:
        """Materialize ``data.workloads.ArrivalEvent`` records as engine
        requests.  Trace *order* is always preserved (admission order is
        then the admission policy's call); arrival *times*
        (``ArrivalEvent.t`` → ``Request.arrives_at``) are replayed only
        when ``gate_arrivals`` is set — otherwise the trace is served as
        a backlog, as fast as slots free up.  SLO annotations
        (``deadline``, ``priority``) ride along for the
        deadline/priority admission policies."""
        return [Request(prompt=ev.prompt, domain=ev.domain,
                        max_new_tokens=ev.max_new_tokens,
                        arrives_at=ev.t,
                        deadline=getattr(ev, "deadline", None),
                        priority=getattr(ev, "priority", 0))
                for ev in trace]

    # ----------------------------------------------------------- lifecycle
    def close(self):
        """Stop the background training service (async mode); buffered
        signals remain drainable.  Idempotent."""
        self.service.close()

    def reset_adaptation(self):
        """Reset every adaptation-side component to its
        post-construction state — draft params, deploy gate, controller,
        channel, signal windows, serving stats — while keeping all
        compiled functions warm.  Benchmarks use this to measure a cold
        adaptive run without paying recompilation.  Holds the service's
        train lock throughout, so an in-flight background cycle
        completes (against the pre-reset gate) before anything is
        cleared and can never publish a stale draft into the fresh
        run."""
        with self.service._train_lock:
            self.channel.reset()
            self.extractor.reset()
            self.controller.reset()
            self.controller.collection_enabled = True   # as in __init__
            self.gate.reset(self._dparams0)
            self.service.reset()
            self.engine.reset_adaptation(
                self._dparams0 if self._serve_device is None
                else jax.device_put(self._dparams0, self._serve_device))

    # ------------------------------------------------------------- stats
    def export_trace(self, path: Optional[str] = None) -> Dict:
        """Export the span tracer's buffer as a Chrome/Perfetto
        trace-event JSON document, writing it to ``path`` (default:
        ``ObsConfig.trace_path``) when one is known."""
        return self.tracer.export(path if path is not None
                                  else self.obs.trace_path)

    def snapshot(self) -> Dict:
        """Flat metrics snapshot across every registry namespace
        (``serving.* / train.* / paging.* / spec.*``).  The legacy
        ``summary()`` keys are views over the same state."""
        return self.metrics.snapshot()

    def summary(self) -> Dict:
        st = self.engine.stats
        return {
            "tokens": st.tokens_out,
            "throughput_tok_s": st.throughput,
            "accept_len": st.accept_len,
            "steps": st.steps,
            "spec_steps": st.spec_steps,
            "refills": st.refills,
            "occupancy": st.occupancy,
            "ttft_p50_s": st.ttft_p50,
            "latency_p95_s": st.latency_p95,
            "idle_supersteps": st.idle_supersteps,
            "deploys": st.deploys,
            "reseeds": st.reseeds,
            "spec_parks": self.engine.policy.speculation.parks,
            "spec_resumes": self.engine.policy.speculation.resumes,
            "train_cycles": len([e for e in self.events
                                 if e["kind"] == "train_cycle"]),
            "deployed": self.gate.version,
            "trainer_failures": getattr(self.service, "failures", 0),
            "signals_collected": self.channel.total_added,
            "signal_bytes": self.channel.total_bytes,
            "signals_dropped": self.channel.dropped,
        }
