"""Rehearsal of chip_smoke.py on the CPU: its serving-and-checking
functions on a tiny bfloat16 config (the pack kernel in interpret mode),
the four-chip decoupled-trainer path on four virtual CPU devices, and its
refusal to run without a TPU."""
import dataclasses
import importlib.util
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny():
    from repro.configs import starcoder2_7b
    return dataclasses.replace(starcoder2_7b.REDUCED, num_layers=8,
                               dtype="bfloat16", param_dtype="bfloat16")


def test_serve_and_check_passes_on_tiny_config(smoke):
    rep = smoke.serve_and_check(_tiny(), n_requests=8, prompt_len=24,
                                max_new=32)
    assert rep["requests"] == 8 and rep["tokens"] == 8 * 32
    assert rep["spec_rounds"] > 0
    assert rep["train_cycles"] >= 1 and rep["deploys"] >= 1
    assert rep["trainer_failures"] == 0
    assert rep["trainer_last_error"] is None
    assert rep["pack_bitwise_equal"]
    assert rep["logit_rel_err"] <= smoke.LOGIT_TOL


def test_logit_check_catches_a_dropped_layer(smoke):
    """The tolerance passes the served bf16 path and fails one that
    skips a layer of the reference model."""
    import jax
    cfg = _tiny()
    params, _ = smoke.init_weights(cfg, 0)
    prompts = jax.random.randint(jax.random.key(1), (2, 24), 0,
                                 cfg.vocab_size)
    want = smoke.reference_logits(cfg, params, prompts)
    ok = smoke.compare_logits(smoke.served_logits(cfg, params, prompts),
                              want)
    assert ok["logit_rel_err"] <= smoke.LOGIT_TOL
    short = dataclasses.replace(cfg, num_layers=7)
    cut = dict(params, body={"pos0": jax.tree.map(
        lambda x: x[:7], params["body"]["pos0"])})
    bad = smoke.compare_logits(smoke.served_logits(short, cut, prompts),
                               want)
    assert bad["logit_rel_err"] > smoke.LOGIT_TOL


def test_decoupled_trainer_on_four_virtual_devices():
    """The --chips 4 path on four CPU devices: async streams equal sync
    streams, the draft trains on the last device and deploys to the
    first."""
    code = ("import dataclasses, sys; sys.path.insert(0, %r); "
            "import chip_smoke as cs; "
            "from repro.configs import starcoder2_7b as S; "
            "cfg = dataclasses.replace(S.REDUCED, num_layers=8, "
            "dtype='bfloat16', param_dtype='bfloat16'); "
            "r = cs.decoupled_vs_sync(cfg, n_requests=16, prompt_len=24, "
            "max_new=64); "
            "print(r['streams_equal'], r['async_trained_on'], "
            "r['async_served_from'])" % ROOT)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split("\n")[-2] == \
        "True ['TFRT_CPU_3'] ['TFRT_CPU_0']"


def test_main_refuses_to_run_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_has_one_location(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache and nothing is
    set in code; otherwise the cache is the fixed <checkout>/.jax_cache."""
    import jax

    from repro.launch.compile_cache import CHECKOUT, enable_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(CHECKOUT, ".jax_cache")
        assert CHECKOUT == ROOT
        assert enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
