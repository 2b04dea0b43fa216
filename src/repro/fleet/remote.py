"""Serving-side endpoints of the out-of-process trainer.

``RemoteTrainingService`` is a drop-in for the in-process
``training.service.TrainingService`` from the serving engine's point of
view — same ``poll``/``drain``/``reset``/``close``/``stats`` surface,
same ``events``/``cycles`` telemetry, same ``_train_lock`` reset
protocol — but every training cycle runs in another process
(``repro.fleet.trainer_main``) on its own XLA client, connected by the
``fleet.wire`` frame protocol.

Serving-path contract (the whole point of disaggregation):

- **signals out** go through ``RemoteSignalChannel`` — the same bounded
  drop-oldest ring as in-process (``SignalChannel`` subclass whose
  ``_prepare`` skips device placement), drained onto the socket by a
  sender thread (async mode) or by ``drain()`` (sync parity mode).
  ``add()`` is an append under a host lock: zero syncs, never blocks on
  the wire, backpressure drops oldest exactly as in-process.
- **drafts in** arrive as DRAFT frames on a receiver thread, which
  ``device_put``s the params off-path and publishes into a
  ``RemoteDeploySource`` — a lock-free newest-wins slot the engine
  polls once per superstep, identical to the in-process deploy slot.

Determinism: in sync parity mode ``drain()`` flushes buffered signals
and a DRAIN barrier over the socket *in one critical section*, and the
trainer emits every DRAFT/EVENT for the barrier's cycles **before** the
DRAIN_ACK on the same ordered stream — so when ``drain()`` returns, the
deploy slot holds exactly what the in-process schedule would have
published, and the serving streams are byte-identical.

Failure model: trainer death (EOF, ECONNRESET, corrupt frame) marks the
service dead, counts a failure, and wakes every waiter — serving
degrades to the last published draft and never hangs; ``close()`` is
idempotent and never raises.
"""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional

from repro.core.signals import SignalBatch
from repro.core.transport import SignalChannel
from repro.fleet import wire
from repro.training.service import DraftVersion


class RemoteSignalChannel(SignalChannel):
    """The in-process drop-oldest signal ring, reused as the socket
    send queue.  Producers (the signal extractor) are unchanged;
    ``_prepare`` keeps batches as host arrays for the sender instead of
    ``device_put``-ing onto a trainer device that lives in another
    process."""

    def __init__(self, capacity: int = 512,
                 spill_dir: Optional[str] = None):
        super().__init__(capacity=capacity, device=None,
                         spill_dir=spill_dir)

    def _prepare(self, batch: SignalBatch) -> SignalBatch:
        return batch    # host arrays; the wire is the placement


class RemoteDeploySource:
    """Lock-free newest-wins slot for drafts received off the wire.
    Callable, so it is a valid engine ``deploy_source`` and a valid
    ``DraftVersionBus`` source."""

    def __init__(self):
        self._latest: Optional[DraftVersion] = None

    def publish(self, ver: DraftVersion):
        cur = self._latest
        if cur is None or ver.seq > cur.seq:
            self._latest = ver

    def poll(self) -> Optional[DraftVersion]:
        return self._latest

    __call__ = poll

    def reset(self):
        self._latest = None


class _GateView:
    """Serving-side mirror of the trainer-process deploy gate: tracks
    the highest published version so ``summary()['deployed']`` and the
    reset protocol keep working without the gate's params."""

    def __init__(self):
        self.version = 0

    def observe(self, seq: int):
        if seq > self.version:
            self.version = seq

    def reset(self, dparams0=None):
        self.version = 0


class RemoteTrainingService:
    """Out-of-process ``TrainingService`` over the fleet wire protocol.

    ``endpoint``: ``"spawn"`` forks a private trainer subprocess on a
    tmp unix socket; ``unix:/path`` / ``tcp:host:port`` connect to a
    running ``python -m repro.fleet.trainer_main``."""

    def __init__(self, endpoint: str, *, tcfg, dcfg, embed_params,
                 dparams0,
                 n_threshold: int = 2048, signal_window: int = 24,
                 train_epochs: int = 2, train_min_steps: int = 80,
                 seed: int = 0, async_train: bool = False,
                 channel_capacity: int = 512,
                 controller=None, selective: bool = False,
                 engine_steps_fn: Optional[Callable[[], int]] = None,
                 poll_s: float = 0.01,
                 connect_timeout: float = 180.0,
                 drain_timeout: float = 600.0,
                 tracer=None, registry=None):
        self.endpoint = endpoint
        self.async_train = async_train
        self.controller = controller
        self.selective = selective
        self.engine_steps_fn = engine_steps_fn or (lambda: -1)
        self.poll_s = poll_s
        self.drain_timeout = drain_timeout
        from repro.obs.trace import DEFAULT_TRACER
        self.tracer = tracer if tracer is not None else DEFAULT_TRACER

        self.channel = RemoteSignalChannel(
            capacity=max(channel_capacity,
                         -(-n_threshold // max(signal_window, 1))))
        self.deploy_source = RemoteDeploySource()
        self.gate = _GateView()
        self.events: List[Dict] = []
        self.cycles = 0
        self.deploys = 0
        self.failures = 0
        self.last_error: Optional[str] = None
        self._trainer_failures = 0   # high-water mark off DRAIN_ACKs
        self.frames_sent = 0
        self.bytes_sent = 0
        self.frames_recv = 0
        self.bytes_recv = 0

        self._train_lock = threading.RLock()
        self._send_lock = threading.Lock()
        self._stop = threading.Event()
        self._closed = threading.Event()
        self._closing = False
        self._dead = False
        self._ready = threading.Event()
        self._acks: Dict[int, Dict] = {}
        self._ack_cond = threading.Condition()
        self._token = 0
        self._sender: Optional[threading.Thread] = None
        self._proc: Optional[subprocess.Popen] = None
        self._tmpdir: Optional[str] = None

        if endpoint == "spawn":
            endpoint = self._spawn()
        self._sock = self._connect_retry(endpoint, connect_timeout)
        hello = {
            "tcfg": wire.config_to_dict(tcfg),
            "dcfg": wire.config_to_dict(dcfg),
            "train": {"n_threshold": int(n_threshold),
                      "signal_window": int(signal_window),
                      "train_epochs": int(train_epochs),
                      "train_min_steps": int(train_min_steps),
                      "seed": int(seed)},
            "async": bool(async_train),
        }
        self._send(wire.FT_HELLO, wire.json_payload(hello))
        init = {f"e/{k}": v
                for k, v in wire.flatten_tree(embed_params).items()}
        init.update({f"p/{k}": v
                     for k, v in wire.flatten_tree(dparams0).items()})
        self._send(wire.FT_INIT, wire.npz_payload(init))
        self._receiver = threading.Thread(target=self._recv_loop,
                                          name="tide-fleet-recv",
                                          daemon=True)
        self._receiver.start()
        if not self._ready.wait(connect_timeout):
            err = self.last_error or "no HELLO ack"
            self.close()
            raise RuntimeError(
                f"trainer at {endpoint} not ready within "
                f"{connect_timeout}s ({err})")
        if registry is not None:
            self.register_metrics(registry)

    # ---------------------------------------------------------- transport
    def _spawn(self) -> str:
        import jax
        backend = jax.default_backend()
        if backend == "tpu":
            # this process holds every local chip, so a child could
            # neither open one nor should it train on the host CPU
            raise RuntimeError(
                "trainer_endpoint='spawn' cannot place a trainer on a TPU "
                "host: the serving process holds the chips, and "
                "per-process chip assignment (ROADMAP Speed 5) is not "
                "implemented; train in process (no trainer_endpoint) or "
                "connect to a trainer running elsewhere")
        self._tmpdir = tempfile.mkdtemp(prefix="tide-fleet-")
        endpoint = f"unix:{os.path.join(self._tmpdir, 'trainer.sock')}"
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = backend     # the child trains where we serve
        import repro
        # namespace package: no __file__, locate via __path__
        src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
        env["PYTHONPATH"] = src + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        self._proc = subprocess.Popen(
            [sys.executable, "-m", "repro.fleet.trainer_main",
             "--listen", endpoint],
            env=env, stdin=subprocess.DEVNULL)
        return endpoint

    def _connect_retry(self, endpoint: str, timeout: float):
        deadline = time.monotonic() + timeout
        while True:
            if self._proc is not None and self._proc.poll() is not None:
                raise RuntimeError(
                    f"trainer subprocess exited with code "
                    f"{self._proc.returncode} before accepting")
            try:
                return wire.connect(endpoint, timeout=1.0)
            except OSError as exc:
                if time.monotonic() >= deadline:
                    raise RuntimeError(
                        f"could not reach trainer at {endpoint} within "
                        f"{timeout}s: {exc}") from exc
                time.sleep(0.05)

    def _send(self, ftype: int, payload: bytes = b""):
        frame = wire.encode_frame(ftype, payload)
        with self._send_lock:
            self._sock.sendall(frame)
            self.frames_sent += 1
            self.bytes_sent += len(frame)

    def _baseline(self) -> float:
        return (self.controller.alpha_train
                if self.controller is not None else 0.0)

    def _mark_dead(self, exc):
        if self._dead or self._closing:
            self._dead = True
        else:
            self._dead = True
            self.failures += 1
            self.last_error = f"{type(exc).__name__}: {exc}"
        self._ready.set()
        with self._ack_cond:
            self._ack_cond.notify_all()

    # ----------------------------------------------------------- receiver
    def _recv_loop(self):
        reader = wire.FrameReader()
        try:
            for ftype, _flags, payload in wire.recv_frames(self._sock,
                                                           reader):
                self.frames_recv += 1
                self.bytes_recv += wire.HEADER.size + len(payload)
                self._handle(ftype, payload)
        except (wire.WireError, OSError, ValueError) as exc:
            self._mark_dead(exc)
            return
        self._mark_dead(RuntimeError("trainer connection closed"))

    def _handle(self, ftype: int, payload: bytes):
        if ftype == wire.FT_HELLO:
            self._ready.set()
        elif ftype == wire.FT_DRAFT:
            seq, dparams, eval_acc = wire.decode_draft(payload)
            import jax
            dparams = jax.device_put(dparams)   # off the serving path
            self.deploy_source.publish(DraftVersion(seq, dparams, eval_acc))
            self.gate.observe(seq)
            self.deploys += 1
            if self.tracer.enabled:
                self.tracer.instant("train.publish", seq=seq,
                                    eval_acc=eval_acc)
        elif ftype == wire.FT_EVENT:
            ev = wire.decode_json(payload)
            if ev.get("kind") == "train_cycle":
                ev["engine_steps"] = self.engine_steps_fn()
                self.events.append(ev)
                self.cycles += 1
                if self.selective and self.controller is not None:
                    self.controller.training_result(ev["eval_acc"])
        elif ftype in (wire.FT_DRAIN_ACK, wire.FT_RESET_ACK):
            ack = wire.decode_json(payload)
            with self._ack_cond:
                self._acks[int(ack.get("token", -1))] = ack
                self._ack_cond.notify_all()
        # HELLO/BYE/others: nothing to do

    def _await_ack(self, token: int, timeout: float) -> Optional[Dict]:
        deadline = time.monotonic() + timeout
        with self._ack_cond:
            while token not in self._acks:
                if self._dead:
                    return None
                left = deadline - time.monotonic()
                if left <= 0:
                    self.failures += 1
                    self.last_error = (f"timed out after {timeout}s "
                                       "waiting for trainer ack")
                    return None
                self._ack_cond.wait(timeout=min(left, 1.0))
            return self._acks.pop(token)

    # ------------------------------------------------------------- sender
    def start(self):
        """Start the background signal sender (async mode).  The
        trainer-side cycle loop was armed by the handshake."""
        if self._sender is not None and self._sender.is_alive():
            return
        self._sender = threading.Thread(target=self._send_loop,
                                        name="tide-fleet-send",
                                        daemon=True)
        self._sender.start()

    def _send_loop(self):
        while not self._stop.is_set() and not self._dead:
            self.channel.wait(1, timeout=self.poll_s)
            if self._stop.is_set() or self._dead:
                break
            try:
                with self._send_lock:
                    batches = self.channel.drain()
                    if batches:
                        self._send_unlocked(
                            wire.FT_SIGNALS,
                            wire.signals_payload(batches,
                                                 self._baseline()))
            except OSError as exc:
                self._mark_dead(exc)
                break

    def _send_unlocked(self, ftype: int, payload: bytes = b""):
        frame = wire.encode_frame(ftype, payload)
        self._sock.sendall(frame)
        self.frames_sent += 1
        self.bytes_sent += len(frame)

    # ------------------------------------------------- service interface
    def poll(self) -> Optional[DraftVersion]:
        """Lock-free read of the latest received deploy (or None)."""
        return self.deploy_source.poll()

    def drain(self) -> int:
        """Deterministic parity barrier: flush buffered signals and run
        every cycle they allow in the trainer process, blocking until
        its DRAIN_ACK.  The trainer emits all DRAFT/EVENT frames for
        those cycles before the ack on the same ordered stream, so the
        deploy slot is final when this returns.  Returns cycles run;
        0 (never a hang) if the trainer is dead."""
        with self._train_lock:
            if self._dead or self._closing:
                return 0
            self._token += 1
            token = self._token
            try:
                with self._send_lock:
                    batches = self.channel.drain()
                    if batches:
                        self._send_unlocked(
                            wire.FT_SIGNALS,
                            wire.signals_payload(batches,
                                                 self._baseline()))
                    self._send_unlocked(
                        wire.FT_DRAIN, wire.json_payload({"token": token}))
            except OSError as exc:
                self._mark_dead(exc)
                return 0
            ack = self._await_ack(token, self.drain_timeout)
            if ack is None:
                return 0
            # trainer-side cycle failures ride back on the ack — mirror
            # them so summary()/stats() make the degradation visible
            # even though the trainer process caught the exception
            tf = int(ack.get("failures", 0))
            if tf > self._trainer_failures:
                self.failures += tf - self._trainer_failures
                self._trainer_failures = tf
                self.last_error = ("trainer-side cycle failure "
                                   "(see trainer process log)")
            return int(ack["cycles"])

    def reset(self):
        """Round-trip reset: clear serving-side mirrors, then reset the
        trainer process (gate back to the initial draft, channel and
        cycle history cleared).  Degrades to a local-only clear if the
        trainer is dead."""
        with self._train_lock:
            self.channel.reset()
            self.deploy_source.reset()
            self.gate.reset()
            self.events.clear()
            self.cycles = 0
            self.deploys = 0
            self.failures = 0
            self.last_error = None
            self._trainer_failures = 0
            if self._dead or self._closing:
                return
            self._token += 1
            token = self._token
            try:
                self._send(wire.FT_RESET, wire.json_payload(
                    {"token": token}))
            except OSError as exc:
                self._mark_dead(exc)
                return
            self._await_ack(token, self.drain_timeout)

    @property
    def running(self) -> bool:
        return (not self._dead and self._receiver.is_alive())

    def kill_trainer(self):
        """Hard-kill a spawned trainer subprocess (failure injection —
        the resilience bench uses this).  Serving must degrade to the
        last published draft, never hang."""
        if self._proc is not None:
            self._proc.kill()

    def close(self, timeout: float = 10.0):
        """Idempotent, never raises, never hangs: best-effort BYE,
        close the socket, join threads with a bound, reap any spawned
        subprocess."""
        if self._closed.is_set():
            return
        self._closed.set()
        self._closing = True
        self._stop.set()
        self.channel.close()
        try:
            self._send(wire.FT_BYE)
        except OSError:
            pass
        try:
            self._sock.shutdown(2)   # SHUT_RDWR — wakes the receiver
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass
        for t in (self._sender, self._receiver):
            if t is not None and t.is_alive():
                t.join(timeout=timeout)
        if self._proc is not None:
            try:
                self._proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self._proc.terminate()
                try:
                    self._proc.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    self._proc.kill()
                    self._proc.wait()
        if self._tmpdir is not None:
            shutil.rmtree(self._tmpdir, ignore_errors=True)
            self._tmpdir = None

    # --------------------------------------------------------------- stats
    def stats(self) -> Dict:
        return {"cycles": self.cycles, "deploy_version": self.gate.version,
                "running": self.running, "trainer_threads": 0,
                "thread_cap": "process",
                "failures": self.failures, "last_error": self.last_error,
                "frames_sent": self.frames_sent,
                "bytes_sent": self.bytes_sent,
                "frames_recv": self.frames_recv,
                "bytes_recv": self.bytes_recv,
                **self.channel.stats()}

    def register_metrics(self, registry):
        registry.gauge("train.cycles", fn=lambda: self.cycles)
        registry.gauge("train.deploy_version",
                       fn=lambda: self.gate.version)
        registry.gauge("train.running", fn=lambda: int(self.running))
        registry.gauge("train.trainer_failures", fn=lambda: self.failures)
        registry.gauge("train.wire_bytes_sent", fn=lambda: self.bytes_sent)
        registry.gauge("train.wire_bytes_recv", fn=lambda: self.bytes_recv)
        self.channel.register_metrics(registry)
