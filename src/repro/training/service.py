"""Decoupled draft-training service (paper §3.3 + §5.5).

Runs ``DraftTrainer.train_cycle`` *off the serving path*: signals
arrive through a bounded ``core.transport.SignalChannel``, cycles run
either on a background thread (single-device hosts — jitted train steps
release the GIL, so training compute fills superstep-boundary and
arrival-gap slack) or on a dedicated training device/submesh
(``transport.pick_training_device``), and every accepted draft is
published as a versioned ``DraftVersion`` into a lock-free
"latest deploy" slot.  The serving engine polls that slot once per
superstep — a Python attribute read, zero extra host↔device syncs —
and hot-swaps the draft in-graph on the next dispatch.

The ``TrainingController`` (Algorithm 1) still decides *whether* a
cycle should run (collection gating, deploy-if-improved); the service
only decides that training never blocks serving.  ``drain()`` is the
deterministic parity mode: called at request-completion boundaries with
the thread disabled, it reproduces the legacy synchronous
``TideSystem`` training schedule byte-for-byte.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, List, NamedTuple, Optional

from repro.checkpoint.ckpt import DraftDeployGate
from repro.core.controller import TrainingController
from repro.core.transport import SignalChannel
from repro.training.draft_trainer import DraftTrainer


class DraftVersion(NamedTuple):
    """One published draft deploy: monotonic sequence number (the deploy
    gate's version counter), the parameters, and the eval acceptance
    that won the gate."""
    seq: int
    dparams: Any
    eval_acc: float


class TrainingService:
    """Asynchronous draft-training loop around a ``DraftTrainer``.

    Thread-safety: ``train_once``/``drain`` are serialized by an
    internal lock (the background loop and an explicit ``drain`` can
    never run a cycle concurrently).  The deploy slot is a single
    attribute published after the gate accepts — readers (the serving
    engine, once per superstep) see either the old or the new
    ``DraftVersion``, never a partial one."""

    def __init__(self, trainer: DraftTrainer, gate: DraftDeployGate,
                 channel: SignalChannel, *,
                 controller: Optional[TrainingController] = None,
                 selective: bool = True,
                 n_threshold: int = 2048, signal_window: int = 24,
                 train_epochs: int = 2, train_min_steps: int = 80,
                 seed: int = 0, publish_device=None,
                 trainer_threads: int = 0,
                 engine_steps_fn: Optional[Callable[[], int]] = None,
                 poll_s: float = 0.05,
                 baseline_fn: Optional[Callable[[], float]] = None,
                 on_publish: Optional[Callable[["DraftVersion"], None]] = None,
                 on_event: Optional[Callable[[Dict], None]] = None,
                 tracer=None, registry=None):
        self.trainer = trainer
        self.gate = gate
        self.channel = channel
        # observability (host-side, thread-safe): train-cycle spans +
        # deploy instants on the shared tracer, ``train.*`` gauges on
        # the shared metrics registry.  Both optional and null-cheap.
        from repro.obs.trace import DEFAULT_TRACER
        self.tracer = tracer if tracer is not None else DEFAULT_TRACER
        if registry is not None:
            self.register_metrics(registry)
        self.controller = controller
        self.selective = selective
        self.n_threshold = n_threshold
        self.signal_window = signal_window
        self.train_epochs = train_epochs
        self.train_min_steps = train_min_steps
        self.seed = seed
        self.publish_device = publish_device
        # trainer-thread contention knob (ServingConfig.trainer_threads):
        # on small single-device hosts the trainer's jitted steps share
        # XLA's intra-op thread pool with serving dispatches, so a cycle
        # slows resident decode by the pool contention factor.  >0
        # deprioritizes the background training thread at the OS
        # scheduler (Linux per-thread nice), so serving dispatches win
        # the shared pool's cores whenever they are runnable.  A true
        # thread-count-limited trainer *client* is only possible
        # out-of-process (the in-process CPU client is one global pool
        # shared with serving — capping it would throttle serving too);
        # that is the ROADMAP follow-on.  ``stats()`` reports the
        # mechanism applied ("thread_nice" or None).
        self.trainer_threads = int(trainer_threads)
        self._thread_cap: Optional[str] = None
        self.engine_steps_fn = engine_steps_fn or (lambda: -1)
        self.poll_s = poll_s
        # disaggregation hooks (repro.fleet.trainer_main): baseline_fn
        # replaces the in-process controller's alpha_train when the
        # controller lives in another process (the serving side ships a
        # best-effort-fresh baseline with each signal frame); on_publish
        # / on_event mirror accepted deploys and cycle events onto the
        # wire.  All optional; the in-process path never sets them.
        self.baseline_fn = baseline_fn
        self.on_publish = on_publish
        self.on_event = on_event
        self.events: List[Dict] = []
        self.cycles = 0
        self.failures = 0
        self.last_error: Optional[str] = None
        self._latest: Optional[DraftVersion] = None   # lock-free slot
        # reentrant: TideSystem.reset_adaptation holds it across a
        # compound reset that includes this service's own reset()
        self._train_lock = threading.RLock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        capacity = getattr(channel, "capacity", None)
        if capacity is not None and capacity < self._min_batches():
            raise ValueError(
                f"SignalChannel capacity {capacity} can never buffer the "
                f"{self._min_batches()} windows one train cycle needs "
                f"(n_threshold={n_threshold} / signal_window="
                f"{signal_window}); training would silently starve")

    # ------------------------------------------------------------ control
    def _deprioritize_thread(self) -> Optional[str]:
        """Lower the background training thread's OS scheduling
        priority (Linux: threads are schedulable tasks, so per-thread
        nice bounds how much of the shared intra-op pool a cycle can
        steal from concurrent serving dispatches)."""
        try:
            tid = threading.get_native_id()
            cur = os.getpriority(os.PRIO_PROCESS, tid)
            os.setpriority(os.PRIO_PROCESS, tid, min(cur + 10, 19))
            return "thread_nice"
        except (AttributeError, OSError, PermissionError):
            return None

    def should_train(self) -> bool:
        """The *whether* gate: enough signal windows buffered for one
        cycle (same trigger arithmetic as the legacy synchronous
        ``TideSystem._maybe_train``)."""
        return (self.channel.peek_count() * self.signal_window
                >= self.n_threshold)

    def _min_batches(self) -> int:
        return max(-(-self.n_threshold // max(self.signal_window, 1)), 1)

    # ----------------------------------------------------------- training
    def train_once(self) -> bool:
        """Run one training cycle if the gate says so; returns whether a
        cycle ran.  Safe from any thread."""
        with self._train_lock:
            if not self.should_train():
                return False
            batches = self.channel.drain()
            if self.controller is not None:
                baseline = self.controller.alpha_train
            elif self.baseline_fn is not None:
                baseline = self.baseline_fn()
            else:
                baseline = 0.0
            # the gate's draft lives where the trainer trains, which
            # places its own arrays: the cycle runs under no default
            # device, so it calls the programs set-up compiled
            dparams, _ = self.gate.current()
            # a cycle under way when a profiler session starts is kept
            # whole: readers clip it to their window
            with self.tracer.long_span("train.cycle",
                                       batches=len(batches)):
                result = self.trainer.train_cycle(
                    dparams, batches, epochs=self.train_epochs,
                    min_steps=self.train_min_steps, seed=self.seed,
                    tracer=self.tracer)
            deployed = self.gate.offer(result["dparams"],
                                       result["eval_acc"], baseline)
            if self.selective and self.controller is not None:
                self.controller.training_result(result["eval_acc"])
            if deployed:
                dp = result["dparams"]
                if self.publish_device is not None:
                    # ship the accepted draft back to the serving device
                    # now, asynchronously — the engine's hot-swap is then
                    # a pure reference swap with no transfer on-path
                    import jax
                    dp = jax.device_put(dp, self.publish_device)
                self._latest = DraftVersion(self.gate.version, dp,
                                            result["eval_acc"])
                if self.on_publish is not None:
                    self.on_publish(self._latest)
                if self.tracer.enabled:
                    self.tracer.instant("train.publish",
                                        seq=self.gate.version,
                                        eval_acc=result["eval_acc"])
            event = {
                "kind": "train_cycle", "eval_acc": result["eval_acc"],
                "train_acc": result["train_acc"], "baseline": baseline,
                "deployed": deployed, "steps": result["steps"],
                "seconds": result["seconds"],
                "engine_steps": self.engine_steps_fn(),
            }
            self.events.append(event)
            if self.on_event is not None:
                self.on_event(event)
            self.cycles += 1
            return True

    def drain(self) -> int:
        """Deterministic parity mode: synchronously run every cycle the
        buffered signals allow (the legacy blocking-training schedule).
        Returns the number of cycles run.

        Safe after trainer death: a cycle that raises is recorded in
        ``failures``/``last_error`` and drain stops (returning the
        cycles that did complete) instead of propagating — serving
        keeps the last published draft and continues (the degradation
        is visible in ``stats()`` and TideSystem ``summary()``)."""
        n = 0
        while True:
            try:
                if not self.train_once():
                    break
            except Exception as exc:  # degrade, don't kill serving
                self._record_failure(exc)
                break
            n += 1
        return n

    def _record_failure(self, exc: Exception):
        self.failures += 1
        self.last_error = f"{type(exc).__name__}: {exc}"

    def poll(self) -> Optional[DraftVersion]:
        """Lock-free read of the latest accepted deploy (or None)."""
        return self._latest

    def reset(self):
        """Clear the deploy slot and cycle history (waits for any
        in-flight cycle; the background thread keeps running)."""
        with self._train_lock:
            self._latest = None
            self.events.clear()
            self.cycles = 0
            self.failures = 0
            self.last_error = None

    # ------------------------------------------------------------- thread
    def start(self):
        """Start (or restart) the background training loop."""
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="tide-draft-training", daemon=True)
        self._thread.start()

    def _loop(self):
        if self.trainer_threads > 0:
            self._thread_cap = self._deprioritize_thread()
        while not self._stop.is_set():
            self.channel.wait(self._min_batches(), timeout=self.poll_s)
            if self._stop.is_set():
                break
            if self.should_train():
                try:
                    self.train_once()
                except Exception as exc:   # trainer died: stop the loop,
                    self._record_failure(exc)   # keep the last deploy
                    break

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def close(self, timeout: float = 30.0):
        """Stop the loop and join the thread.  Idempotent and safe
        after abrupt trainer death: a thread that fails to join within
        ``timeout`` (e.g. wedged inside a dead trainer's cycle) is
        abandoned and counted in ``failures`` — close never raises and
        never hangs serving shutdown.  The channel is closed (waking
        any blocked waiter) but its buffered signals remain
        drainable."""
        self._stop.set()
        self.channel.close()
        t, self._thread = self._thread, None
        if t is not None and t.is_alive():
            t.join(timeout=timeout)
            if t.is_alive():
                self._record_failure(RuntimeError(
                    f"training thread failed to stop within {timeout}s; "
                    "abandoned (daemon)"))

    # --------------------------------------------------------------- stats
    def stats(self) -> Dict:
        return {"cycles": self.cycles, "deploy_version": self.gate.version,
                "running": self.running,
                "trainer_threads": self.trainer_threads,
                "thread_cap": self._thread_cap,
                "failures": self.failures, "last_error": self.last_error,
                **self.channel.stats()}

    def register_metrics(self, registry):
        """Expose the service (and its channel) under the ``train.*``
        metrics namespace as callback gauges — the legacy ``stats()``
        dict stays as a thin view over the same state."""
        registry.gauge("train.cycles", fn=lambda: self.cycles)
        registry.gauge("train.deploy_version",
                       fn=lambda: self.gate.version)
        registry.gauge("train.running", fn=lambda: int(self.running))
        self.channel.register_metrics(registry)
