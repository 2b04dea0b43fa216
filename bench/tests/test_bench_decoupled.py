"""The decoupled cell's whole run on four virtual CPU devices (a
subprocess, since the device count is fixed when JAX starts), at the 15B
block's tiny shape: a sound run is correct, compiles nothing in its
window, trains on the last device and serves from the first.  And the
configuration's reference computes on a device the program leaves
idle."""
import json
import os
import subprocess
import sys

import jax
import pytest

from bench.configs import starcoder2_decoupled

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

RUN = r"""
import json, sys, time
import jax
from bench import arrivals, cell

DATA = "bench/tests/data"
bm = dict(cell.load_benchmark())
bm["configs"] = [{"name": "tiny-15b", "file": DATA + "/tiny-15b.json"}]
bm["workloads"] = [{"name": "tiny.decoupled", "config": "tiny-15b",
                    "traffic": "tiny-chat-decoupled", "chips": 4}]
seen = {"trained_on": set(), "served_from": set(),
        "referenced_on": set()}

def where(tree):
    return {d.id for x in jax.tree.leaves(tree) for d in x.devices()}

def probe(sys_):
    # after set-up: watch where every train step of the stream runs, and
    # from where the engine serves
    tr, step = sys_.trainer, sys_.trainer._step
    def watched(*args):
        out = step(*args)
        seen["trained_on"] |= where(out[0])
        seen["served_from"] |= where(sys_.engine.dparams)
        return out
    tr._step = watched

# where the reference computes its layers and its draft steps
from bench.configs import starcoder2
for name in ("_layer", "_adamw_step"):
    def on(*args, _f=getattr(starcoder2, name)):
        out = _f(*args)
        seen["referenced_on"] |= where(out)
        return out
    setattr(starcoder2, name, on)

res = cell.run("tiny.decoupled", 2 ** 31 + 5, 2.0, False,
               t_proc=time.perf_counter(), require_tpu=False, bench=bm,
               mix=arrivals.load_mix("tiny-chat-decoupled", DATA),
               fault=probe)
print(json.dumps({"correct": res.line["correct"],
                  "checks": res.line["checks"],
                  "compiles_in_window": res.notes["compiles_in_window"],
                  "train_cycles": res.notes["train_cycles"],
                  **{k: sorted(v) for k, v in seen.items()}}))
"""


@pytest.fixture(scope="module")
def decoupled_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", RUN], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_decoupled_run_is_correct(decoupled_run):
    assert decoupled_run["correct"] is True, decoupled_run["checks"]
    assert decoupled_run["compiles_in_window"] == 0
    assert decoupled_run["train_cycles"] >= 1


def test_decoupled_run_trains_on_last_device(decoupled_run):
    assert decoupled_run["trained_on"] == [3]
    assert decoupled_run["served_from"] == [0]


def test_decoupled_reference_computes_on_an_idle_device(decoupled_run):
    """The reference's layers and draft steps run on neither the serving
    device nor the trainer's."""
    assert decoupled_run["referenced_on"] == [1]


@pytest.mark.parametrize("count,want", [(1, None), (2, None), (3, 1),
                                         (4, 1)])
def test_reference_device_leaves_serving_and_training_chips(
        monkeypatch, count, want):
    """The reference computes on the second device when the host has one
    besides the serving (first) and the training (last) device."""
    monkeypatch.setattr(jax, "devices", lambda: list(range(count)))
    assert starcoder2_decoupled.reference_device() == want
