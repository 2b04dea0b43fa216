"""Disaggregated serving subsystem (repro/fleet): router + bus units,
the TrainerHost wire protocol over a socketpair (stub service — no
XLA), RemoteTrainingService against an in-process trainer host, and the
TrainingService failure paths the remote topology leans on.

Slow tier: real spawned trainer subprocess (drain parity, kill
degradation) and the N-replica ServingFleet end-to-end.
"""
import socket
import threading
import time
import types

import numpy as np
import pytest

from repro.core.signals import SignalBatch
from repro.core.transport import SignalChannel
from repro.fleet import FleetConfig, wire
from repro.fleet.bus import DraftVersionBus
from repro.fleet.remote import (RemoteDeploySource, RemoteSignalChannel,
                                RemoteTrainingService, _GateView)
from repro.fleet.router import FleetRouter, request_cost
from repro.fleet.trainer_main import TrainerHost
from repro.serving.request import Request
from repro.training.service import DraftVersion, TrainingService


def _batch(i, s=8, f=6):
    return SignalBatch(feats=np.full((s, f), i, np.float32),
                       tokens=np.full((s,), i, np.int32))


# ===================================================== config + router
def test_fleet_config_validation():
    assert not FleetConfig().enabled
    assert FleetConfig(replicas=2).enabled
    assert FleetConfig(trainer_endpoint="spawn").enabled
    with pytest.raises(ValueError):
        FleetConfig(replicas=-1)
    with pytest.raises(ValueError):
        FleetConfig(route="random")


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_spawn_trains_on_the_parents_platform(monkeypatch, backend):
    """A spawned trainer inherits the serving process's platform; on a
    TPU host, where this process holds the chips, spawn refuses loudly
    instead of training on the host CPU."""
    import subprocess

    import jax
    spawned = []

    class _Exited:
        returncode = 1

        def __init__(self, cmd, env, **kw):
            spawned.append(env)

        def poll(self):
            return self.returncode

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(subprocess, "Popen", _Exited)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError) as err:
        RemoteTrainingService("spawn", tcfg=None, dcfg=None,
                              embed_params={}, dparams0={})
    if backend == "tpu":
        assert "Speed 5" in str(err.value) and not spawned
    else:
        assert "exited with code 1" in str(err.value)
        assert [env["JAX_PLATFORMS"] for env in spawned] == ["cpu"]


def test_router_least_loaded_balances():
    r = FleetRouter(2, "least")
    big = Request(prompt=[1] * 8, max_new_tokens=100)
    small = Request(prompt=[1] * 8, max_new_tokens=10)
    assert r.assign(big) == 0           # tie -> lowest index
    assert r.assign(small) == 1
    assert r.assign(small) == 1         # 11 + 11 < 101
    assert r.assign(small) == 1
    assert r.load[0] == pytest.approx(request_cost(big))
    assert r.assigned == [1, 3]


def test_router_round_robin_and_split_order():
    r = FleetRouter(3, "rr")
    reqs = [Request(prompt=[1], max_new_tokens=i + 1) for i in range(7)]
    shards = r.split(reqs)
    assert [len(s) for s in shards] == [3, 2, 2]
    # arrival order preserved within each shard
    assert [q.max_new_tokens for q in shards[0]] == [1, 4, 7]
    assert [q.max_new_tokens for q in shards[1]] == [2, 5]


def test_router_validation():
    with pytest.raises(ValueError, match="replica"):
        FleetRouter(0)
    with pytest.raises(ValueError, match="policy"):
        FleetRouter(2, "hash")


# ============================================================== the bus
def test_bus_newest_wins_fanout_and_idempotent_subscribe():
    bus = DraftVersionBus()
    a, b = bus.subscribe("r0"), bus.subscribe("r1")
    assert bus.subscribe("r0") is a
    assert a() is None
    bus.publish(DraftVersion(2, {"w": 2}, 0.5))
    bus.publish(DraftVersion(1, {"w": 1}, 0.4))   # stale: ignored
    assert bus.published == 1
    assert a().seq == 2 and b().seq == 2
    assert a().seq == 2                           # repeat poll: same version
    assert a.deliveries == 1 and a.delivered_seq == 2
    bus.publish(DraftVersion(3, {"w": 3}, 0.6))
    assert b().seq == 3 and b.deliveries == 2
    st = bus.stats()
    assert st["latest_seq"] == 3 and st["published"] == 2
    assert st["subscribers"]["r0"]["delivered_seq"] == 2


def test_bus_pulls_from_upstream_source():
    slot = RemoteDeploySource()
    bus = DraftVersionBus(source=slot.poll)
    sub = bus.subscribe("r0")
    assert sub() is None
    slot.publish(DraftVersion(1, {"w": 1}, 0.5))
    assert sub().seq == 1 and bus.published == 1
    slot.publish(DraftVersion(5, {"w": 5}, 0.9))
    slot.publish(DraftVersion(4, {"w": 4}, 0.8))  # stale at the slot too
    assert sub().seq == 5


def test_remote_deploy_source_and_gate_view():
    slot = RemoteDeploySource()
    slot.publish(DraftVersion(3, {"w": 3}, 0.5))
    assert slot() is slot.poll() and slot().seq == 3
    slot.reset()
    assert slot.poll() is None
    gate = _GateView()
    gate.observe(2)
    gate.observe(1)
    assert gate.version == 2
    gate.reset()
    assert gate.version == 0


def test_remote_signal_channel_keeps_host_arrays():
    ch = RemoteSignalChannel(capacity=2)
    for i in range(3):
        ch.add(_batch(i))
    assert ch.dropped == 1 and ch.peek_count() == 2
    kept = ch.drain()
    assert isinstance(kept[0].feats, np.ndarray), \
        "remote channel must not device_put onto a local device"
    assert [int(b.tokens[0]) for b in kept] == [1, 2]


# ============================= TrainingService failure paths (satellite)
class _RaisingTrainer:
    def __init__(self, exc):
        self.exc = exc

    def train_cycle(self, dparams, batches, **kw):
        raise self.exc


class _BlockingTrainer:
    def __init__(self):
        self.started = threading.Event()
        self.release = threading.Event()

    def train_cycle(self, dparams, batches, **kw):
        self.started.set()
        self.release.wait(timeout=30.0)
        return {"dparams": dparams, "train_acc": 0.0, "eval_acc": 0.0,
                "steps": 1, "seconds": 0.0}


def _gate():
    from repro.checkpoint.ckpt import DraftDeployGate
    return DraftDeployGate({"w": np.zeros(2, np.float32)})


def test_service_drain_survives_trainer_death():
    """drain() after the trainer dies mid-cycle: the failure is counted,
    the buffered signals are consumed, serving-side state stays usable,
    and close() is clean — never a hang or a propagated exception."""
    ch = SignalChannel(capacity=8)
    svc = TrainingService(_RaisingTrainer(RuntimeError("trainer died")),
                          _gate(), ch, n_threshold=8, signal_window=8,
                          selective=False)
    ch.add(_batch(0))
    assert svc.drain() == 0
    assert svc.failures == 1
    assert "RuntimeError: trainer died" in svc.last_error
    assert svc.drain() == 0 and svc.failures == 1   # signals consumed
    st = svc.stats()
    assert st["failures"] == 1 and "trainer died" in st["last_error"]
    svc.close()
    svc.close()                                     # idempotent
    svc.reset()
    assert svc.failures == 0 and svc.last_error is None


def test_service_background_loop_stops_on_trainer_death():
    ch = SignalChannel(capacity=8)
    svc = TrainingService(_RaisingTrainer(ValueError("boom")), _gate(),
                          ch, n_threshold=8, signal_window=8,
                          selective=False, poll_s=0.01)
    svc.start()
    ch.add(_batch(0))
    for _ in range(200):
        if not svc.running:
            break
        time.sleep(0.02)
    assert not svc.running, "loop must stop after the trainer raises"
    assert svc.failures == 1 and "boom" in svc.last_error
    svc.close()


def test_service_close_abandons_wedged_thread():
    """A cycle wedged inside a dead trainer must not hang shutdown:
    close() times out the join, counts a failure, and returns."""
    trainer = _BlockingTrainer()
    ch = SignalChannel(capacity=8)
    svc = TrainingService(trainer, _gate(), ch, n_threshold=8,
                          signal_window=8, selective=False, poll_s=0.01)
    svc.start()
    ch.add(_batch(0))
    assert trainer.started.wait(timeout=10.0), "cycle never started"
    svc.close(timeout=0.2)
    assert svc.failures == 1 and "abandoned" in svc.last_error
    svc.close(timeout=0.2)                          # idempotent
    trainer.release.set()                           # let the daemon die


# =========================== TrainerHost protocol (socketpair, no XLA)
class _StubChannel:
    def __init__(self):
        self.batches = []

    def add(self, b):
        self.batches.append(b)

    def reset(self):
        self.batches.clear()


class _StubService:
    """Protocol-level stand-in for TrainingService inside TrainerHost:
    drain publishes one draft + one event back through the host, so the
    DRAFT/EVENT-before-DRAIN_ACK ordering is observable."""

    def __init__(self, hello, embed, dparams0, host):
        self.hello, self.embed, self.dparams0 = hello, embed, dparams0
        self.host = host
        self.channel = _StubChannel()
        self.gate = types.SimpleNamespace(version=0,
                                          reset=lambda dp=None: None)
        self.failures = 0
        self._train_lock = threading.RLock()
        self.drains = self.resets = self.closed = 0
        self.started = False

    def drain(self):
        self.drains += 1
        self.gate.version += 1
        self.host.send_draft(DraftVersion(
            self.gate.version,
            {"fc": {"w": np.full(3, self.gate.version, np.float32)}},
            0.75))
        self.host.send_event({"kind": "train_cycle", "eval_acc": 0.75,
                              "train_acc": 0.7,
                              "baseline": self.host.baseline,
                              "deployed": True, "steps": 3,
                              "seconds": 0.01, "dropme": object()})
        return 1

    def reset(self):
        self.resets += 1

    def start(self):
        self.started = True

    def close(self):
        self.closed += 1


def _handshake_frames(async_train=False):
    from conftest import tiny_cfg
    from repro.core import eagle
    cfg = tiny_cfg()
    hello = {"tcfg": wire.config_to_dict(cfg),
             "dcfg": wire.config_to_dict(eagle.draft_config(cfg)),
             "train": {"n_threshold": 8, "signal_window": 8,
                       "train_epochs": 1, "train_min_steps": 2,
                       "seed": 0},
             "async": async_train}
    init = {"e/w": np.zeros((4, 2), np.float32),
            "p/fc/w": np.ones(3, np.float32)}
    return (wire.encode_frame(wire.FT_HELLO, wire.json_payload(hello))
            + wire.encode_frame(wire.FT_INIT, wire.npz_payload(init)))


def _run_host(conn, holder):
    host = TrainerHost(conn, service_factory=_StubService)
    holder["host"] = host
    try:
        host.run()
    except Exception as exc:            # surfaced by the test
        holder["err"] = exc
    finally:
        conn.close()


def _recv_n(sock, reader, n, timeout=10.0):
    sock.settimeout(timeout)
    out = []
    while len(out) < n:
        out.extend(reader.feed(sock.recv(1 << 16)))
    return out


def test_trainer_host_protocol_roundtrip():
    """Full protocol over a socketpair: handshake ack, SIGNALS ingest
    with the baseline riding along, DRAFT + EVENT strictly before the
    DRAIN_ACK on the same stream, RESET round trip, BYE shutdown (which
    closes the service)."""
    client, server = socket.socketpair()
    holder = {}
    t = threading.Thread(target=_run_host, args=(server, holder),
                         daemon=True)
    t.start()
    reader = wire.FrameReader()
    client.sendall(_handshake_frames())
    (ftype, _f, payload), = _recv_n(client, reader, 1)
    assert ftype == wire.FT_HELLO and wire.decode_json(payload)["ok"]
    stub = holder["host"].service
    assert stub.hello["train"]["n_threshold"] == 8
    assert stub.embed["w"].shape == (4, 2)
    assert not stub.started                     # sync handshake

    client.sendall(wire.encode_frame(
        wire.FT_SIGNALS,
        wire.signals_payload([_batch(3)], baseline=0.375)))
    client.sendall(wire.encode_frame(
        wire.FT_DRAIN, wire.json_payload({"token": 7})))
    frames = _recv_n(client, reader, 3)
    assert [f[0] for f in frames] == \
        [wire.FT_DRAFT, wire.FT_EVENT, wire.FT_DRAIN_ACK], \
        "drafts/events must precede the drain ack on the stream"
    seq, dparams, acc = wire.decode_draft(frames[0][2])
    assert seq == 1 and acc == 0.75
    np.testing.assert_array_equal(dparams["fc"]["w"],
                                  np.full(3, 1, np.float32))
    event = wire.decode_json(frames[1][2])
    assert event["kind"] == "train_cycle"
    assert event["baseline"] == 0.375           # shipped with SIGNALS
    assert "dropme" not in event                # non-scalars filtered
    ack = wire.decode_json(frames[2][2])
    assert ack == {"token": 7, "cycles": 1, "version": 1, "failures": 0}
    # the SIGNALS frame landed in the trainer-side channel, losslessly
    assert len(stub.channel.batches) == 1
    np.testing.assert_array_equal(stub.channel.batches[0].feats,
                                  _batch(3).feats)

    client.sendall(wire.encode_frame(
        wire.FT_RESET, wire.json_payload({"token": 8})))
    (ftype, _f, payload), = _recv_n(client, reader, 1)
    assert ftype == wire.FT_RESET_ACK
    assert wire.decode_json(payload)["token"] == 8
    assert stub.resets == 1 and stub.channel.batches == []
    assert holder["host"].baseline == 0.0       # reset clears it

    client.sendall(wire.encode_frame(wire.FT_BYE))
    t.join(timeout=10.0)
    assert not t.is_alive() and "err" not in holder
    assert stub.closed == 1
    client.close()


def test_trainer_host_async_handshake_starts_service():
    client, server = socket.socketpair()
    holder = {}
    t = threading.Thread(target=_run_host, args=(server, holder),
                         daemon=True)
    t.start()
    reader = wire.FrameReader()
    client.sendall(_handshake_frames(async_train=True))
    _recv_n(client, reader, 1)
    assert holder["host"].service.started
    client.sendall(wire.encode_frame(wire.FT_BYE))
    t.join(timeout=10.0)
    client.close()


def test_trainer_host_rejects_out_of_order_handshake():
    client, server = socket.socketpair()
    holder = {}
    t = threading.Thread(target=_run_host, args=(server, holder),
                         daemon=True)
    t.start()
    client.sendall(wire.encode_frame(
        wire.FT_SIGNALS, wire.signals_payload([_batch(0)])))
    t.join(timeout=10.0)
    assert isinstance(holder.get("err"), wire.WireError)
    assert "expected HELLO" in str(holder["err"])
    client.close()


def test_trainer_host_eof_before_handshake():
    client, server = socket.socketpair()
    holder = {}
    t = threading.Thread(target=_run_host, args=(server, holder),
                         daemon=True)
    t.start()
    client.close()
    t.join(timeout=10.0)
    assert isinstance(holder.get("err"), wire.WireError)
    assert "closed before HELLO" in str(holder["err"])


# ================== RemoteTrainingService against an in-process host
def _tiny_handshake_args():
    from conftest import tiny_cfg
    from repro.core import eagle
    cfg = tiny_cfg()
    return dict(tcfg=cfg, dcfg=eagle.draft_config(cfg),
                embed_params={"w": np.zeros((4, 2), np.float32)},
                dparams0={"fc": {"w": np.ones(3, np.float32)}},
                n_threshold=8, signal_window=8, connect_timeout=30.0,
                drain_timeout=30.0)


def _host_thread(endpoint, holder):
    srv = wire.listen(endpoint)
    holder["srv"] = srv

    def serve():
        conn, _ = srv.accept()
        _run_host(conn, holder)

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    return t


def test_remote_service_drain_draft_pickup_and_close(tmp_path):
    """The serving-side endpoint against a live (stub) trainer host:
    drain() flushes signals + barrier, and by the time it returns the
    DRAFT published during the barrier is in the deploy slot and the
    event/cycle mirrors are updated — the drain-parity ordering
    contract.  close() is idempotent and tears the host down via BYE."""
    ep = f"unix:{tmp_path}/t.sock"
    holder = {}
    t = _host_thread(ep, holder)
    svc = RemoteTrainingService(ep, engine_steps_fn=lambda: 42,
                                **_tiny_handshake_args())
    try:
        assert svc.running and svc.poll() is None
        svc.channel.add(_batch(5))
        assert svc.drain() == 1
        ver = svc.poll()
        assert ver is not None and ver.seq == 1 and ver.eval_acc == 0.75
        np.testing.assert_array_equal(np.asarray(ver.dparams["fc"]["w"]),
                                      np.full(3, 1, np.float32))
        assert svc.gate.version == 1 and svc.deploys == 1
        assert svc.cycles == 1
        assert svc.events[0]["kind"] == "train_cycle"
        assert svc.events[0]["engine_steps"] == 42
        stub = holder["host"].service
        assert len(stub.channel.batches) == 1
        assert svc.drain() == 1                  # empty flush still cycles
        st = svc.stats()
        assert st["thread_cap"] == "process" and st["trainer_threads"] == 0
        assert st["frames_sent"] >= 4 and st["frames_recv"] >= 4
        assert st["failures"] == 0

        svc.reset()
        assert svc.poll() is None and svc.cycles == 0
        assert svc.gate.version == 0
        assert holder["host"].service.resets == 1
    finally:
        svc.close()
        svc.close()                              # idempotent
        t.join(timeout=10.0)
        holder["srv"].close()
    assert holder["host"].service.closed == 1
    assert not svc.running


def test_remote_service_trainer_death_degrades_not_hangs(tmp_path):
    """Abrupt trainer death after the handshake: the receiver marks the
    service dead, drain() returns 0 promptly, reset() degrades to a
    local clear, the failure is counted, and close() stays clean."""
    ep = f"unix:{tmp_path}/t.sock"
    holder = {}
    t = _host_thread(ep, holder)
    svc = RemoteTrainingService(ep, **_tiny_handshake_args())
    try:
        holder["host"].conn.shutdown(socket.SHUT_RDWR)   # trainer "dies"
        for _ in range(200):
            if not svc.running:
                break
            time.sleep(0.02)
        assert not svc.running
        assert svc.failures >= 1 and svc.last_error is not None
        svc.channel.add(_batch(0))
        t0 = time.monotonic()
        assert svc.drain() == 0
        assert time.monotonic() - t0 < 5.0, "dead drain must not hang"
        svc.reset()                                      # local-only clear
        assert svc.poll() is None
    finally:
        svc.close()
        t.join(timeout=10.0)
        holder["srv"].close()


def test_remote_service_connect_failure_is_clean(tmp_path):
    with pytest.raises(RuntimeError, match="could not reach"):
        RemoteTrainingService(f"unix:{tmp_path}/nobody.sock",
                              **{**_tiny_handshake_args(),
                                 "connect_timeout": 0.3})


# ======================================================= slow: real e2e
@pytest.fixture(scope="module")
def pretrained():
    import jax
    import repro.configs as C
    from repro.core import eagle
    from repro.data.workloads import make_domains, training_corpus
    from repro.models import transformer as T
    from repro.training.trainer import pretrain_target

    cfg = C.get("tide-tiny")
    params = T.init(cfg, jax.random.key(0))
    domains = make_domains(cfg.vocab_size, ["science"], branchings=[2],
                           seed=3)
    corpus = training_corpus(domains["science"], 64, 40, 1)
    params, _ = pretrain_target(cfg, params, corpus, steps=80, lr=3e-3)
    dcfg = eagle.draft_config(cfg)
    dparams = eagle.draft_init(dcfg, jax.random.key(7))
    return cfg, params, dcfg, dparams, domains


_FLEET_TCFG = dict(gamma=3, batch_size=2, max_len=96, adaptive_spec=False,
                   selective_training=False, signal_window=8, n_threshold=4,
                   train_epochs=1, train_min_steps=6, seed=0)


def _reqs(domains, budgets, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt=domains["science"].sample_prompt(rng),
                    max_new_tokens=m, domain="science") for m in budgets]


def _strip(events):
    return [{k: v for k, v in e.items() if k != "seconds"}
            for e in events]


@pytest.mark.slow
def test_fleet_streams_match_single_engine(pretrained):
    """Two data-parallel replicas behind the router/bus serve the exact
    per-request greedy streams a single engine serves (draft- and
    scheduling-invariance), the replicas share compiled step functions,
    and reset_adaptation makes the fleet run reproducible."""
    from repro.core.tide import TideConfig, TideSystem
    from repro.fleet.router import ServingFleet

    cfg, params, dcfg, dparams, domains = pretrained
    budgets = (24, 16, 24, 12, 20, 24, 16, 24)

    single = TideSystem(cfg, params, TideConfig(**_FLEET_TCFG),
                        dparams=dparams)
    ref = _reqs(domains, budgets, seed=11)
    single.run_stream(iter(ref))
    single.close()

    tc = TideConfig(**_FLEET_TCFG, fleet=FleetConfig(replicas=2))
    fleet = ServingFleet(cfg, params, tc, dparams=dparams)
    assert fleet.engines[1]._superstep_fn is fleet.engines[0]._superstep_fn
    assert fleet.engines[1]._prefill_fn is fleet.engines[0]._prefill_fn
    got = _reqs(domains, budgets, seed=11)
    done = fleet.serve(got)
    assert len(done) == len(ref)
    assert sorted((tuple(r.prompt), tuple(r.generated)) for r in got) == \
        sorted((tuple(r.prompt), tuple(r.generated)) for r in ref)

    s = fleet.summary()
    assert s["replicas"] == 2
    assert all(n > 0 for n in s["router_assigned"]), \
        "least-loaded routing must use both replicas"
    assert s["tokens"] == sum(s["replica_tokens"])
    assert s["train_cycles"] >= 1 and s["deployed"] >= 1
    assert s["bus"]["published"] >= 1
    assert s["trainer_failures"] == 0

    fleet.reset_adaptation()
    again = _reqs(domains, budgets, seed=11)
    fleet.serve(again)
    assert [tuple(r.generated) for r in again] == \
        [tuple(r.generated) for r in got]
    s2 = fleet.summary()
    assert s2["router_assigned"] == s["router_assigned"]
    fleet.close()


@pytest.mark.slow
def test_remote_spawn_drain_parity(pretrained):
    """The acceptance gate: a spawned out-of-process trainer in sync
    (drain-parity) mode reproduces the in-process system byte-for-byte —
    token streams, cycle counts, deploy versions, and the train-cycle
    event stream (timing excluded)."""
    from repro.core.tide import TideConfig, TideSystem

    cfg, params, dcfg, dparams, domains = pretrained
    budgets = (24, 16, 24, 20)

    ref_sys = TideSystem(cfg, params, TideConfig(**_FLEET_TCFG),
                         dparams=dparams)
    ref = _reqs(domains, budgets, seed=5)
    ref_sys.run_stream(iter(ref))
    assert ref_sys.service.cycles >= 1, "scenario never trained"

    tc = TideConfig(**_FLEET_TCFG,
                    fleet=FleetConfig(trainer_endpoint="spawn"))
    rem_sys = TideSystem(cfg, params, tc, dparams=dparams)
    got = _reqs(domains, budgets, seed=5)
    try:
        rem_sys.run_stream(iter(got))
        assert [r.generated for r in got] == [r.generated for r in ref]
        assert rem_sys.service.cycles == ref_sys.service.cycles
        assert rem_sys.gate.version == ref_sys.gate.version
        ref_ev, rem_ev = _strip(ref_sys.events), _strip(rem_sys.events)
        assert len(rem_ev) == len(ref_ev)
        for a, b in zip(rem_ev, ref_ev):
            assert a["deployed"] == b["deployed"]
            assert a["steps"] == b["steps"]
            assert a["engine_steps"] == b["engine_steps"]
            assert a["baseline"] == b["baseline"]
            assert a["eval_acc"] == pytest.approx(b["eval_acc"], abs=1e-6)
            assert a["train_acc"] == pytest.approx(b["train_acc"],
                                                   abs=1e-6)
        assert rem_sys.summary()["trainer_failures"] == 0
    finally:
        rem_sys.close()
        ref_sys.close()


@pytest.mark.slow
def test_remote_spawn_trainer_kill_degrades(pretrained):
    """Kill the trainer subprocess mid-workload: serving completes every
    request on the last deployed draft, drain() never hangs, and the
    degradation is visible in summary()."""
    from repro.core.tide import TideConfig, TideSystem

    cfg, params, dcfg, dparams, domains = pretrained
    tc = TideConfig(**_FLEET_TCFG,
                    fleet=FleetConfig(trainer_endpoint="spawn"))
    sys_ = TideSystem(cfg, params, tc, dparams=dparams)
    try:
        first = _reqs(domains, (24, 16), seed=9)
        sys_.run_stream(iter(first))
        sys_.service.kill_trainer()
        for _ in range(300):
            if not sys_.service.running:
                break
            time.sleep(0.05)
        assert not sys_.service.running
        second = _reqs(domains, (20, 24, 12), seed=10)
        t0 = time.monotonic()
        done = sys_.run_stream(iter(second))
        assert len(done) == 3
        assert all(len(r.generated) > 0 for r in second)
        assert time.monotonic() - t0 < 120.0
        assert sys_.service.drain() == 0
        assert sys_.summary()["trainer_failures"] >= 1
    finally:
        sys_.close()
        sys_.close()                             # idempotent
