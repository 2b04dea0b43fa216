"""The decoupled draft trainer on a device of its own, on four virtual
CPU devices (a subprocess, since the device count is fixed when JAX
starts): its step and eval take arguments from any device and compile
once each, set-up's calls included; the deploy gate's drafts live on the
trainer's device and the engine's on the serving one.  Without a device
of its own the trainer calls its jitted programs as they are."""
import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = r"""
import json, sys
import jax, jax.numpy as jnp, numpy as np
import repro.configs as C
from repro.core import eagle
from repro.core.signals import SignalBatch
from repro.core.tide import TideConfig, TideSystem
from repro.core.transport import pick_training_device
from repro.models import transformer as T
from repro.training.draft_trainer import DraftTrainer

def where(tree):
    return sorted({d.id for x in jax.tree.leaves(tree) for d in x.devices()})

devs = jax.devices()
cfg = C.get("tide-tiny")
dcfg = eagle.draft_config(cfg)
params = jax.device_put(T.init(cfg, jax.random.key(0)), devs[0])
dparams = jax.device_put(eagle.draft_init(dcfg, jax.random.key(7)), devs[0])
out = {"train_device": pick_training_device().id}

# set-up's calls, every argument on the serving device, then one cycle
# from a draft on the trainer's device
tr = DraftTrainer(cfg, dcfg, jax.device_put(params["embed"], devs[3]),
                  device=devs[3])
s, f = 8, 3 * cfg.d_model
step = tr._step(dparams, tr.opt.init(dparams), params["embed"],
                jnp.zeros((8, s, f), cfg.act_dtype),
                jnp.zeros((8, s), jnp.int32), jnp.int32(0))
tr._eval(dparams, params["embed"], jnp.zeros((1, s, f), cfg.act_dtype),
         jnp.zeros((1, s), jnp.int32))
out["warm_step_on"] = where(step[0])
rng = np.random.default_rng(0)
batches = [SignalBatch(rng.standard_normal((s, f)).astype(np.float32),
                       rng.integers(0, cfg.vocab_size, s).astype(np.int32))
           for _ in range(12)]
res = tr.train_cycle(jax.device_put(dparams, devs[3]), batches,
                     epochs=1, min_steps=3)
out["cycle_steps"] = res["steps"]
out["cycle_on"] = where(res["dparams"])
out["step_compiles"] = tr._step.__wrapped__._cache_size()
out["eval_compiles"] = tr._eval.__wrapped__._cache_size()

sys_ = TideSystem(cfg, params, TideConfig(batch_size=2, max_len=64,
                  async_train=True), dparams=dparams)
try:
    out["trainer_device"] = sys_.trainer.device.id
    out["gate_on"] = where(sys_.gate.current()[0])
    out["engine_on"] = where(sys_.engine.dparams)
    sys_.reset_adaptation()
    out["gate_on_after_reset"] = where(sys_.gate.current()[0])
    out["engine_on_after_reset"] = where(sys_.engine.dparams)
finally:
    sys_.close()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def probe():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", PROBE], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_trainer_compiles_step_and_eval_once_from_any_device(probe):
    assert probe["train_device"] == 3
    assert probe["warm_step_on"] == [3]
    assert probe["cycle_steps"] == 3 and probe["cycle_on"] == [3]
    assert probe["step_compiles"] == 1
    assert probe["eval_compiles"] == 1


def test_gate_draft_on_trainer_device_engine_draft_on_serving(probe):
    assert probe["trainer_device"] == 3
    assert probe["gate_on"] == probe["gate_on_after_reset"] == [3]
    assert probe["engine_on"] == probe["engine_on_after_reset"] == [0]


def test_trainer_without_device_calls_its_jitted_programs():
    import repro.configs as C
    from repro.core import eagle
    from repro.models import transformer as T
    from repro.training.draft_trainer import DraftTrainer
    cfg = C.get("tide-tiny")
    params = jax.eval_shape(lambda: T.init(cfg, jax.random.key(0)))
    tr = DraftTrainer(cfg, eagle.draft_config(cfg), params["embed"])
    jitted = type(jax.jit(lambda x: x))
    assert tr.device is None
    assert type(tr._step) is jitted and type(tr._eval) is jitted


def test_extractor_ships_windows_inside_a_span():
    """Each hand-off of full windows to the store is one ``signals.ship``
    span on the tracer, around the store's adds."""
    import numpy as np

    from repro.core.signals import SignalExtractor, SignalStore
    from repro.obs.trace import Tracer
    tr = Tracer()
    store = SignalStore()
    ex = SignalExtractor(store, window=4, tracer=tr)
    feats = np.zeros((1, 6, 3), np.float32)
    toks = np.arange(6, dtype=np.int32)[None]
    ex.ingest_packed([7], feats, toks, [3])     # 3 pairs: no window yet
    assert not tr.events()
    ex.ingest_packed([7], feats, toks, [6])     # 9 pairs: two windows
    ships = [e for e in tr.events() if e[1] == "signals.ship"]
    assert len(ships) == 1 and store.total_added == 2
