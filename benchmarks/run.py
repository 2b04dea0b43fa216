# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per paper table/figure:

  Fig. 4   bench_beta_ratio          β(b) verification-latency ratio
  Fig. 5/6 bench_adaptation          accept-length/throughput over time
  Fig. 8   bench_speedup_model       Eq. 5 predicted vs actual speedup
  Fig. 9   bench_adaptive_control    TIDE-default vs TIDE-adaptive
  Fig.10-12 bench_hetero             heterogeneous allocation model
  Table 1  bench_storage             hidden-state storage math
  Table 2  bench_training_time       reuse vs recompute training time
  Table 3  bench_cross_domain        cross-dataset acceptance matrix
  Table 4  bench_gamma_sweep         (batch, γ) configuration sweep
  Table 5  bench_profile_latency     T(n)/D0 profiles
  (g)      bench_roofline            dry-run roofline table
  kernels  bench_kernels             kernel oracles + TPU rooflines

Run all: ``PYTHONPATH=src python -m benchmarks.run``
Run one: ``PYTHONPATH=src python -m benchmarks.run --only table2``

Besides the CSV stream, each run writes a machine-readable report —
``BENCH_smoke.json`` / ``BENCH_full.json`` (or ``--bench-out PATH``) —
with per-bench status, wall seconds, emitted metric rows, and the
overall pass/fail gate, so CI and regression tooling can diff runs
without scraping stdout.  ``--only`` runs skip the default report (a
filtered run is not comparable) unless ``--bench-out`` names one.

``--check`` additionally compares this run's **round-domain** metrics
(the ``BASELINE_KEYS`` allowlist — deterministic hit rates, counters,
and gated ratios; never wall-clock) against the committed
``benchmarks/BENCH_baseline.json``, appends the verdict to
``benchmarks/BENCH_history.jsonl``, and exits nonzero on drift.
``--update-baseline`` rewrites the baseline from the current run:

    PYTHONPATH=src python -m benchmarks.run --only overload --check
    PYTHONPATH=src python -m benchmarks.run --only overload \\
        --update-baseline
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

MODULES = [
    ("hotloop", "benchmarks.bench_hotloop"),
    ("continuous", "benchmarks.bench_continuous"),
    ("decoupled", "benchmarks.bench_decoupled"),
    ("slo", "benchmarks.bench_slo"),
    ("overload", "benchmarks.bench_overload"),
    ("paged", "benchmarks.bench_paged"),
    ("tree", "benchmarks.bench_tree"),
    ("fleet", "benchmarks.bench_fleet"),
    ("table5", "benchmarks.bench_profile_latency"),
    ("fig4", "benchmarks.bench_beta_ratio"),
    ("table1", "benchmarks.bench_storage"),
    ("table2", "benchmarks.bench_training_time"),
    ("table3", "benchmarks.bench_cross_domain"),
    ("table4", "benchmarks.bench_gamma_sweep"),
    ("fig8", "benchmarks.bench_speedup_model"),
    ("fig5", "benchmarks.bench_adaptation"),
    ("fig9", "benchmarks.bench_adaptive_control"),
    ("fig10", "benchmarks.bench_hetero"),
    ("roofline", "benchmarks.bench_roofline"),
    ("kernels", "benchmarks.bench_kernels"),
]


# Fast CI perf-smoke gate: the serving hot-loop overhead bench (reduced
# shapes) + the continuous-batching goodput/parity gate (including the
# long-prompt chunked-refill scenario: byte parity, the deterministic
# max-prefill-op-width stall bound, and the modeled-goodput gate) + the
# decoupled async-training gate (>=1.2x serving vs blocking training +
# drain parity) + the serving-policy SLO gate (EDF deadline-hit-rate
# >= 1.2x FIFO, eager-commit short-prompt TTFT, stream byte parity, no
# added syncs) + the overload-resilience gate (preemptive weighted-EDF
# deadline-hit-rate >= 1.3x non-preemptive EDF at ~4x overload, bounded
# p99, byte-identical restored streams greedy and sampled, zero leaked
# pages, no added syncs) + the paged-KV gate (>= 4x served slots at the dense HBM
# footprint with zero deferrals, dense/paged stream byte parity greedy
# and sampled, prefix-sharing registry hits with <= 0.7x prefill
# row-token work, zero leaked pages after drain) + the tree-speculation
# gate (accepted draft tokens per target pass >= 1.2x the linear chain
# at equal passes, tokens/s uplift reported with a conservative CPU
# floor, width=1 engine streams byte-identical to the chain, zero
# leaked pages with paging on) + the disaggregation gate (N=4 replica
# fleet >= 3x single-replica critical-path rounds with byte-identical
# streams and full bus fan-out, out-of-process trainer drain-parity
# byte-identical with no added serving-path syncs, trainer-kill
# degradation completes every request) + the kernel oracles.
# ``python -m benchmarks.run --smoke``.
SMOKE_MODULES = [
    ("hotloop", "benchmarks.bench_hotloop"),
    ("continuous", "benchmarks.bench_continuous"),
    ("decoupled", "benchmarks.bench_decoupled"),
    ("slo", "benchmarks.bench_slo"),
    ("overload", "benchmarks.bench_overload"),
    ("paged", "benchmarks.bench_paged"),
    ("tree", "benchmarks.bench_tree"),
    ("fleet", "benchmarks.bench_fleet"),
    ("kernels", "benchmarks.bench_kernels"),
]

# ------------------------------------------------- baseline regression
# Round-domain metric keys pinned by ``--check`` against the committed
# ``benchmarks/BENCH_baseline.json``.  Only deterministic round-clock
# keys are eligible — never wall-clock keys (0.8-2.5x noise on this
# shared host), and never accept-rate-dependent keys like raw round
# counts (the smoke-mode draft trains fewer steps than full, so its
# makespan differs; hit rates, preempt/restore counters, and the gated
# ratios are invariant by trace design).  ``--update-baseline``
# rewrites the file from the current run restricted to these keys.
BASELINE_KEYS = {
    "overload/preempt/base": ["hit_rate", "tight_hit_rate"],
    "overload/preempt/wedf": ["hit_rate", "tight_hit_rate",
                              "preemptions", "restores"],
    "overload/preempt/ratio": ["hit_gain", "p99_ratio", "sync_ratio"],
    "overload/preempt/sampled": ["preemptions", "restores", "parity"],
    "overload/preempt/paged": ["preemptions", "restores",
                               "spilled_pages", "parity"],
    # fleet keys are structural: parity flags, the replica count, the
    # counter-derived sync ratio (~1.0 by construction), and the gated
    # round-domain speedup (trace-design-invariant up to draft accept
    # rate, hence the wider tolerance)
    "fleet/ratio": ["round_speedup", "parity", "replicas"],
    "fleet/remote": ["parity", "sync_ratio", "trainer_failures"],
    "fleet/kill": ["parity", "trainer_failures"],
}
# per-key relative tolerance overrides written into the baseline file:
# the p99/sync ratios sit near 1.0 by construction but their exact
# value shifts a little with the draft's accept rate
BASELINE_TOLS = {
    "overload/preempt/ratio:p99_ratio": 0.15,
    "overload/preempt/ratio:sync_ratio": 0.15,
    "fleet/ratio:round_speedup": 0.2,
    "fleet/remote:sync_ratio": 0.05,
}
BASELINE_PATH = "benchmarks/BENCH_baseline.json"
HISTORY_PATH = "benchmarks/BENCH_history.jsonl"
_DEFAULT_TOL = 0.02     # relative; counters compare exactly via this


def _parse_derived(derived: str) -> dict:
    """``k=v;k=v`` derived strings -> {key: float} (trailing units like
    the ``x`` of ratio values are stripped; non-numeric values skipped)."""
    out = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v.rstrip("x"))
        except ValueError:
            pass
    return out


def _live_metrics(rows) -> dict:
    live = {}
    for name, _us, derived in rows:
        live.setdefault(name, {}).update(_parse_derived(derived))
    return live


def _check_baseline(path: str, rows) -> tuple:
    """Compare this run's round-domain metrics against the committed
    baseline.  Returns (failures, n_compared)."""
    with open(path) as f:
        base = json.load(f)
    live = _live_metrics(rows)
    tols = base.get("tolerances", {})
    failures, compared = [], 0
    for name, keys in base["metrics"].items():
        got_row = live.get(name)
        if got_row is None:
            failures.append(f"{name}: row missing from this run")
            continue
        for key, want in keys.items():
            compared += 1
            got = got_row.get(key)
            tol = tols.get(f"{name}:{key}", base.get("tolerance",
                                                     _DEFAULT_TOL))
            if got is None:
                failures.append(f"{name}:{key}: key missing")
            elif abs(got - want) > tol * max(abs(want), 1.0):
                failures.append(
                    f"{name}:{key}: {got:g} vs baseline {want:g} "
                    f"(tol {tol:g})")
    return failures, compared


def _update_baseline(path: str, rows) -> None:
    live = _live_metrics(rows)
    metrics = {}
    for name, keys in BASELINE_KEYS.items():
        row = live.get(name)
        if row is None:
            continue
        picked = {k: row[k] for k in keys if k in row}
        if picked:
            metrics[name] = picked
    doc = {"schema": "tide-bench-baseline/v1",
           "tolerance": _DEFAULT_TOL,
           "tolerances": {k: v for k, v in BASELINE_TOLS.items()
                          if k.split(":")[0] in metrics},
           "metrics": metrics}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# baseline -> {path} ({sum(map(len, metrics.values()))} "
          f"keys)", flush=True)


def _append_history(path: str, mode: str, failed, check_failures,
                    compared: int) -> None:
    entry = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
             "mode": mode, "passed": not (failed or check_failures),
             "failed_benches": failed, "checked_keys": compared,
             "check_failures": check_failures}
    with open(path, "a") as f:
        json.dump(entry, f, sort_keys=True)
        f.write("\n")
    print(f"# history -> {path}", flush=True)


def _write_report(path: str, mode: str, benches: list,
                  failed: list) -> None:
    """Write the machine-readable run report: per-bench status/seconds/
    metric rows plus the overall gate verdict."""
    doc = {
        "schema": "tide-bench-report/v1",
        "mode": mode,
        "passed": not failed,
        "failed": failed,
        "benches": benches,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# report -> {path}", flush=True)


def main() -> None:
    import inspect

    from benchmarks import common

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on the bench tag")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI perf-smoke: hotloop + kernels only, "
                         "reduced shapes")
    ap.add_argument("--bench-out", default=None, metavar="PATH",
                    help="machine-readable JSON report path (default: "
                         "BENCH_smoke.json / BENCH_full.json; --only "
                         "runs write no report unless this is given)")
    ap.add_argument("--check", action="store_true",
                    help="after the run, compare round-domain metrics "
                         f"against {BASELINE_PATH} and append the "
                         f"verdict to {HISTORY_PATH}; exits nonzero on "
                         "regression")
    ap.add_argument("--baseline", default=BASELINE_PATH, metavar="PATH",
                    help="baseline file for --check/--update-baseline")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from this run (restricted "
                         "to the BASELINE_KEYS round-domain allowlist)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    modules = SMOKE_MODULES if args.smoke else MODULES
    mode = "smoke" if args.smoke else "full"
    out = args.bench_out
    if out is None and not args.only:
        out = f"BENCH_{mode}.json"
    print("name,us_per_call,derived")
    failed = []
    benches = []
    for tag, module in modules:
        if args.only and args.only not in tag:
            continue
        t0 = time.perf_counter()
        row0 = len(common.ROWS)
        print(f"# === {tag} ({module}) ===", flush=True)
        error = None
        try:
            fn = __import__(module, fromlist=["run"]).run
            kw = {}
            if args.smoke and "smoke" in inspect.signature(fn).parameters:
                kw["smoke"] = True
            fn(**kw)
        except Exception:
            failed.append(tag)
            error = traceback.format_exc()
            print(f"# {tag} FAILED:", file=sys.stderr)
            traceback.print_exc()
        dt = time.perf_counter() - t0
        benches.append({
            "tag": tag, "module": module,
            "status": "failed" if error else "passed",
            "seconds": round(dt, 3),
            "error": error,
            "metrics": [{"name": n, "us_per_call": round(us, 3),
                         "derived": d}
                        for n, us, d in common.ROWS[row0:]],
        })
        print(f"# === {tag} done in {dt:.1f}s ===", flush=True)
    if out:
        _write_report(out, mode, benches, failed)
    if args.update_baseline:
        _update_baseline(args.baseline, common.ROWS)
    check_failures, compared = [], 0
    if args.check:
        check_failures, compared = _check_baseline(args.baseline,
                                                   common.ROWS)
        for msg in check_failures:
            print(f"# CHECK FAILED {msg}", file=sys.stderr)
        print(f"# check: {compared} keys vs {args.baseline}, "
              f"{len(check_failures)} regressions", flush=True)
        _append_history(HISTORY_PATH, mode, failed, check_failures,
                        compared)
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")
    if check_failures:
        raise SystemExit(
            f"baseline regression: {len(check_failures)} metric(s) "
            f"drifted (see CHECK FAILED lines)")


if __name__ == '__main__':
    main()
