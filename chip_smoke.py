"""Bring-up smoke run: TIDE's serving + online draft-training path on a TPU
at StarCoder2-7B widths (d_model 4608, 36 query / 4 KV heads of 128,
d_ff 18432, vocab 49152), cut in depth to fit one v5e
(``repro.configs.starcoder2_7b.ONE_CHIP``), with seeded random weights.

It drives the system through the entry points a user calls —
``TideSystem.requests_from_trace`` -> ``run_stream`` -> ``summary`` — and
fails unless every request is served in full, speculation ran, a draft
was trained and deployed without a trainer failure, the signal-pack
kernel matches its oracle bitwise on a real verify round, and served
logits agree with a float32 forward pass.

  python chip_smoke.py            # one chip: serve, train, deploy, check
  python chip_smoke.py --chips 4  # only the decoupled trainer on its own
                                  # chip, compared with sync mode

The last line of a passing run is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  With no TPU
it exits non-zero at the start and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

GAMMA = 3
N_REQUESTS = 16
PROMPT_LEN = 256          # one length: one prefill shape to compile
MAX_NEW = 64
SEED = 0
# The served path keeps activations in bfloat16 (unit roundoff 2^-9) and
# accumulates in float32; the reference is float32 throughout on the same
# (bfloat16-valued) weights.  Rounding about six activations per layer
# over 8 layers leaves a relative error near sqrt(48) * 2^-9 ~ 1.4% in
# the residual stream, and the largest of ~10^5 logits may sit 4-5 times
# above that, so the max logit error is held to 10% of the reference's
# RMS logit.  An 8-bit float (roundoff 2^-4, 16x larger) or a dropped
# layer fails it.
LOGIT_TOL = 0.10


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed; the message lists each one."""


def log(msg: str):
    print(msg, flush=True)


def memory_plan(cfg) -> dict:
    """Bytes the one-chip run must hold, from the configs' own counts."""
    import jax.numpy as jnp

    from repro.core import eagle
    wb = jnp.dtype(cfg.param_dtype).itemsize
    dcfg = eagle.draft_config(cfg)
    target = cfg.param_count() * wb
    draft = eagle.draft_param_count(dcfg) * wb
    # AdamW: updated params + grads in the weight dtype, two f32 moments
    trainer = eagle.draft_param_count(dcfg) * (2 * wb + 8)
    return {"target_gb": target / 1e9, "draft_gb": draft / 1e9,
            "trainer_gb": trainer / 1e9,
            "total_gb": (target + draft + trainer) / 1e9}


def make_requests(sys_, vocab: int, *, n: int, prompt_len: int,
                  max_new: int, seed: int):
    from repro.data.workloads import arrival_trace, make_domains
    domains = make_domains(vocab, ["science", "code"], branchings=[2, 3],
                           seed=seed + 3)
    trace = arrival_trace(domains, n, mode="poisson", rate=16.0,
                          max_new_range=(max_new, max_new),
                          prompt_len=(prompt_len, prompt_len),
                          seed=seed + 1)
    return sys_.requests_from_trace(trace)


def tide_config(cfg, *, prompt_len: int, max_new: int, seed: int,
                async_train: bool = False):
    from repro.core.tide import TideConfig
    max_len = -(-(prompt_len + max_new + 2 * (GAMMA + 1)) // 8) * 8
    # speculation forced on: with random weights the Eq. 5 gate would
    # turn it off and verify would never run.  A train cycle starts once
    # 16 windows of 8 accepted positions are buffered: short windows, so
    # the held-out eval windows come from requests the cycle trained on.
    return TideConfig(gamma=GAMMA, batch_size=4, max_len=max_len,
                      adaptive_spec=False, signal_window=8, n_threshold=16,
                      train_epochs=2, train_min_steps=40, seed=seed,
                      async_train=async_train)


def init_weights(cfg, seed: int):
    import jax

    from repro.core import eagle
    from repro.models import transformer as T
    params = T.init(cfg, jax.random.key(seed))
    dparams = eagle.draft_init(eagle.draft_config(cfg),
                               jax.random.key(seed + 7))
    # committed to the serving chip, as weights loaded for serving are:
    # a trainer on another chip must move what it reads
    return jax.device_put((params, dparams), jax.devices()[0])


def check_stream(reqs, vocab: int, max_new: int) -> list:
    bad = []
    for r in reqs:
        toks = list(r.generated)
        if len(toks) != max_new:
            bad.append(f"request {r.rid}: {len(toks)}/{max_new} tokens")
        elif not all(0 <= t < vocab for t in toks):
            bad.append(f"request {r.rid}: token out of [0, {vocab})")
    return bad


def check_pack(cfg, params, dparams, prompts) -> dict:
    """One real verify round (prefill, draft seed, one speculative step),
    then its (captures, tokens, mask) through ``pack_signals`` — the
    kernel; interpret mode off a TPU — against ``extract_pack_ref``,
    bitwise, with the round's own mask and with a seeded scattered one
    (the superstep's masks are prefixes, which barely compact)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import eagle
    from repro.core import speculative as spec
    from repro.kernels.extract_pack.ops import pack_signals
    from repro.kernels.extract_pack.ref import extract_pack_ref
    from repro.models import transformer as T
    dcfg = eagle.draft_config(cfg)

    @jax.jit
    def one_round(params, dparams, toks):
        b, s = toks.shape
        max_len = s + 2 * (GAMMA + 1)
        pre = T.prefill(cfg, params, toks, max_len=max_len)
        first = pre["logits"].argmax(-1).astype(jnp.int32)
        dcache = eagle.init_draft_cache(dcfg, b, max_len)
        dcache = spec.seed_draft_cache(cfg, dcfg, params, dparams, dcache,
                                       pre, toks)
        carry = spec.init_carry(cfg, dcfg, pre, first, GAMMA)
        out = spec.spec_decode_step(cfg, dcfg, params, dparams,
                                    pre["cache"], dcache, carry,
                                    gamma=GAMMA)
        return out["captures"], out["tokens"], out["accept_mask"]

    caps, toks, mask = one_round(params, dparams, prompts)
    scattered = jnp.asarray(
        np.random.default_rng(SEED).random(mask.shape) < 0.5)
    pack = jax.jit(lambda f, t, m: pack_signals(f, t, m, force_kernel=True))
    kernel = "tpu_custom_call" in pack.lower(caps, toks, mask).compile(
        ).as_text()
    equal = True
    for m in (mask.astype(bool), scattered):
        got = jax.device_get(pack(caps, toks, m))
        want = jax.device_get(jax.jit(extract_pack_ref)(caps, toks, m))
        equal &= all(np.array_equal(np.asarray(a).view(np.uint8),
                                    np.asarray(b).view(np.uint8))
                     for a, b in zip(got, want))
    return {"pack_bitwise_equal": bool(equal), "pack_on_kernel": kernel,
            "pack_shape": list(caps.shape)}


def served_logits(cfg, params, toks):
    """Next-token logits the way the engine serves them: prefill S-1
    positions (padded path), then one cached decode step."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T

    @jax.jit
    def run(params, toks):
        b, s = toks.shape
        pre = T.prefill(cfg, params, toks[:, :-1], max_len=s,
                        pad=jnp.zeros((b,), jnp.int32))
        dec = T.decode_step(cfg, params, pre["cache"], toks[:, -1:],
                            want_caps=False)
        return dec["logits"][:, 0]

    return run(params, toks)


def reference_logits(cfg, params, toks):
    """The same logits from a float32 forward pass over all S positions
    at the highest matmul precision, on the same weights."""
    import jax

    from repro.models import transformer as T
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: T.prefill(
            cfg32, p, t, want_caps=False)["logits"])(params, toks)


def compare_logits(got, want) -> dict:
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    err = float(np.max(np.abs(got - want)))
    rms = float(np.sqrt(np.mean(np.square(want))))
    return {"logit_max_err": err, "logit_ref_rms": rms,
            "logit_rel_err": err / rms, "logit_tol": LOGIT_TOL,
            "logits_finite": bool(np.isfinite(got).all())}


def serve_and_check(cfg, *, n_requests: int = N_REQUESTS,
                    prompt_len: int = PROMPT_LEN, max_new: int = MAX_NEW,
                    seed: int = SEED) -> dict:
    """Serve ``n_requests`` through TideSystem in sync-training mode, then
    run the pack-kernel and logit checks.  Returns the report; raises
    ``SmokeFailure`` naming every check that failed."""
    import jax
    import jax.numpy as jnp

    from repro.core.tide import TideSystem
    params, dparams = init_weights(cfg, seed)
    sys_ = TideSystem(cfg, params, tide_config(
        cfg, prompt_len=prompt_len, max_new=max_new, seed=seed),
        dparams=dparams)
    reqs = make_requests(sys_, cfg.vocab_size, n=n_requests,
                         prompt_len=prompt_len, max_new=max_new, seed=seed)
    t0 = time.perf_counter()
    done = sys_.run_stream(reqs)
    jax.block_until_ready(sys_.engine.dparams)
    wall = time.perf_counter() - t0
    sys_.close()
    s = sys_.summary()
    svc = sys_.service
    report = {"serve_wall_s": wall, "requests": len(done),
              "tokens": s["tokens"], "spec_rounds": s["spec_steps"],
              "rounds": s["steps"], "accept_len": s["accept_len"],
              "train_cycles": s["train_cycles"], "deploys": s["deploys"],
              "trainer_failures": svc.failures,
              "trainer_last_error": svc.last_error}
    failed = check_stream(reqs, cfg.vocab_size, max_new)
    if len(done) != n_requests:
        failed.append(f"{len(done)}/{n_requests} requests completed")
    if s["spec_steps"] <= 0:
        failed.append("no speculative round ran")
    if s["train_cycles"] < 1:
        failed.append("no train cycle completed")
    if s["deploys"] < 1:
        failed.append("no draft deploy landed")
    if svc.failures or svc.last_error is not None:
        failed.append(f"trainer failed {svc.failures} time(s): "
                      f"{svc.last_error}")
    # the checks read the draft the engine served last
    prompts = jnp.asarray([r.prompt for r in reqs[:2]], jnp.int32)
    report.update(check_pack(cfg, params, sys_.engine.dparams, prompts))
    if not report["pack_bitwise_equal"]:
        failed.append("pack_signals differs from extract_pack_ref")
    if jax.default_backend() == "tpu" and not report["pack_on_kernel"]:
        failed.append("pack_signals did not lower to the Pallas kernel")
    report.update(compare_logits(served_logits(cfg, params, prompts),
                                 reference_logits(cfg, params, prompts)))
    if not (report["logits_finite"]
            and report["logit_rel_err"] <= LOGIT_TOL):
        failed.append(f"logit error {report['logit_rel_err']:.4g} of the "
                      f"reference RMS exceeds {LOGIT_TOL}")
    if failed:
        raise SmokeFailure("; ".join(failed))
    return report


def decoupled_vs_sync(cfg, *, n_requests: int = N_REQUESTS,
                      prompt_len: int = PROMPT_LEN, max_new: int = MAX_NEW,
                      seed: int = SEED) -> dict:
    """The decoupled-trainer path: ``async_train=True`` with the trainer
    on the chip ``pick_training_device`` gives it, serving two halves of
    the requests with a train cycle between them, against the same
    requests served in sync mode.  Greedy speculative output does not
    depend on the draft, so the streams must match exactly."""
    import jax

    from repro.core.tide import TideSystem
    from repro.core.transport import pick_training_device
    params, dparams = init_weights(cfg, seed)
    streams = {}
    placement = {}
    for mode in ("sync", "async"):
        sys_ = TideSystem(cfg, params, tide_config(
            cfg, prompt_len=prompt_len, max_new=max_new, seed=seed,
            async_train=mode == "async"), dparams=dparams)
        reqs = make_requests(sys_, cfg.vocab_size, n=n_requests,
                             prompt_len=prompt_len, max_new=max_new,
                             seed=seed)
        half = n_requests // 2
        sys_.run_stream(reqs[:half])
        if mode == "async":
            # train on what the first half buffered: the second half then
            # picks the deploy up mid-stream
            sys_.service.drain()
        sys_.run_stream(reqs[half:])
        sys_.close()
        streams[mode] = [list(r.generated) for r in reqs]
        s = sys_.summary()
        placement[mode] = {
            "deploys": s["deploys"], "train_cycles": s["train_cycles"],
            "failures": sys_.service.failures,
            "last_error": sys_.service.last_error,
            "trained_on": sorted({str(d) for leaf in jax.tree.leaves(
                sys_.gate.current()[0]) for d in leaf.devices()}),
            "served_from": sorted({str(d) for leaf in jax.tree.leaves(
                sys_.engine.dparams) for d in leaf.devices()}),
        }
        del sys_
    want_train = str(pick_training_device())
    want_serve = str(jax.devices()[0])
    a = placement["async"]
    failed = []
    if streams["async"] != streams["sync"]:
        n = sum(x != y for x, y in zip(streams["async"], streams["sync"]))
        failed.append(f"{n}/{n_requests} async streams differ from sync")
    if a["deploys"] < 1:
        failed.append("no deploy landed in async mode")
    if a["failures"] or a["last_error"] is not None:
        failed.append(f"async trainer failed: {a['last_error']}")
    if a["trained_on"] != [want_train]:
        failed.append(f"trained draft on {a['trained_on']}, "
                      f"expected {want_train}")
    if a["served_from"] != [want_serve]:
        failed.append(f"deployed draft on {a['served_from']}, "
                      f"expected {want_serve}")
    report = {"streams_equal": streams["async"] == streams["sync"],
              "train_device": want_train, "serve_device": want_serve,
              **{f"{m}_{k}": v for m, p in placement.items()
                 for k, v in p.items()}}
    if failed:
        raise SmokeFailure("; ".join(failed))
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the decoupled-trainer path on a "
                         "four-chip host")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke: no TPU (found {dev[0].platform}); the smoke "
              "run measures the chip and has no CPU mode", file=sys.stderr)
        return 2
    if len(dev) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(dev)} device(s)",
              file=sys.stderr)
        return 2

    from repro.configs.starcoder2_7b import ONE_CHIP, ONE_CHIP_CUTS
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    cfg = ONE_CHIP
    log(f"device: {dev[0].device_kind} x{len(dev)}; config {cfg.name}: "
        f"d_model {cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}"
        f"x{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{cfg.num_layers} layers, {cfg.param_dtype} weights")
    for key, why in ONE_CHIP_CUTS.items():
        log(f"  cut {key}: {why}")
    plan = memory_plan(cfg)
    limit = dev[0].memory_stats().get("bytes_limit", 0)
    log("memory plan (GB): " + ", ".join(f"{k} {v:.2f}"
                                         for k, v in plan.items())
        + f"; device bytes_limit {limit / 1e9:.2f}")

    compile_s = {"trace": 0.0, "lower": 0.0, "backend": 0.0}
    phases = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "backend"}

    def on_duration(event, secs, **_):
        if event in phases:
            compile_s[phases[event]] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            report = decoupled_vs_sync(cfg)
        else:
            report = serve_and_check(cfg)
    except SmokeFailure as exc:
        log(f"FAILED: {exc}")
        return 1
    report["total_wall_s"] = time.perf_counter() - t0
    report["compile_s"] = compile_s
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0) for d in dev]
    report["peak_bytes_in_use"] = peaks if args.chips == 4 else peaks[0]
    for k, v in report.items():
        log(f"{k}: {v}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
