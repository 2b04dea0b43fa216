"""Serving launcher: runs the full TIDE system (adaptive speculative
decoding + online draft training) on a reduced config, live on the local
device(s).  ``--dryrun`` lowers the full config's speculative serve step
on the production mesh instead.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch tide-tiny --requests 48
  PYTHONPATH=src python -m repro.launch.serve --arch tide-tiny --continuous
  PYTHONPATH=src python -m repro.launch.serve --arch tide-tiny --tree-width 4
  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-v3-671b --dryrun

``--continuous`` serves a ragged Poisson arrival trace through the
continuous-batching ``serve_stream`` loop (in-flight slot refill)
instead of run-to-completion waves, and reports goodput, slot
occupancy, and TTFT/latency percentiles.

Every ``ServingConfig`` field has a flag here (and a flat
``TideConfig`` mirror) — ``build_parser``/``config_from_args`` are the
one mapping, asserted total by tests/test_config_mirror.py.
"""
from __future__ import annotations

import argparse
import time


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tide-tiny")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--gamma", type=int, default=3)
    ap.add_argument("--max-len", type=int, default=0,
                    help="engine cache length (0 = auto: 96 for waves, "
                         "160 for --continuous)")
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--sample", action="store_true",
                    help="per-request-keyed sampled decoding instead of "
                         "greedy argmax")
    ap.add_argument("--superstep-rounds", type=int, default=8,
                    help="speculative rounds fused per superstep "
                         "dispatch (0 = per-step reference loop)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop token id (default: budget-only stop)")
    ap.add_argument("--accept-ema", type=float, default=0.9,
                    help="acceptance-length EMA decay for the Eq. 5 gate")
    ap.add_argument("--seed", type=int, default=0,
                    help="engine base seed (per-request sampling streams)")
    ap.add_argument("--tree-width", type=int, default=0,
                    help=">=1: tree speculation — draft W top-k "
                         "branches, each gamma deep, verified in one "
                         "tree-masked target pass; the longest accepted "
                         "root path commits (1 = degenerate tree, "
                         "bitwise equal to the chain; 0 = chain)")
    ap.add_argument("--pretrain-steps", type=int, default=120)
    ap.add_argument("--no-adaptive", action="store_true")
    ap.add_argument("--continuous", action="store_true",
                    help="serve a ragged Poisson arrival trace with "
                         "in-flight slot refill instead of waves")
    ap.add_argument("--async-train", action="store_true",
                    help="decoupled draft training: background service, "
                         "zero-sync versioned deploys + draft-cache "
                         "re-seed (default: synchronous drain at "
                         "completion boundaries)")
    ap.add_argument("--gate-arrivals", action="store_true",
                    help="replay trace arrival timestamps (idle "
                         "supersteps in gaps) instead of serving the "
                         "trace as a backlog; implies --continuous")
    ap.add_argument("--idle-wait-s", type=float, default=0.005,
                    help="max host sleep per gated-arrival idle tick")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked refill prefill width (multiple of 8; "
                         "0 = one-shot): bounds the stall a long prompt "
                         "injects into resident decode lanes to one "
                         "chunk per superstep gap")
    ap.add_argument("--page-size", type=int, default=0,
                    help=">0: paged KV cache — target/draft caches "
                         "become block-table page pools with admission-"
                         "time reservations and COW prompt-prefix "
                         "sharing (must divide max_len; 0 = dense)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page-pool size (0 = the dense footprint, "
                         "batch * max_len / page_size)")
    ap.add_argument("--no-share-prefix", action="store_true",
                    help="disable COW prompt-prefix page sharing")
    ap.add_argument("--reseed-window", type=int, default=None,
                    help="deploy-time draft-cache re-seed ring size "
                         "(default: 32 under --async-train, else 0; "
                         "paged engines re-seed through the lanes' "
                         "block-table rows in place)")
    ap.add_argument("--policy",
                    choices=["fifo", "priority", "deadline", "wedf"],
                    default="fifo",
                    help="admission policy: fifo (arrival order), "
                         "priority (highest Request.priority first), "
                         "deadline (EDF over Request.deadline — the "
                         "latency-SLO policy), or wedf (EDF with the "
                         "deadline relaxed by priority weight); implies "
                         "--continuous for non-fifo choices")
    ap.add_argument("--preempt", choices=["none", "deadline"],
                    default="none",
                    help="preemption policy (docs/overload.md): deadline "
                         "spills the loosest resident lane to host when "
                         "a tighter-deadline candidate is deferred "
                         "against a full batch, restoring it byte-"
                         "identically once a lane frees (superstep "
                         "mode only)")
    ap.add_argument("--shed", choices=["none", "expired", "queue"],
                    default="none",
                    help="load-shedding policy: expired drops queued "
                         "requests whose deadline already passed; queue "
                         "bounds the arrived queue depth, dropping the "
                         "loosest deadlines first")
    ap.add_argument("--shed-queue-depth", type=int, default=64,
                    help="arrived-queue depth bound for --shed queue")
    ap.add_argument("--commit", choices=["cohort", "eager"],
                    default="cohort",
                    help="chunk-pipeline commit policy: cohort (default; "
                         "an admission batch's pipelines land together, "
                         "densest decode rounds) or eager (each pipeline "
                         "commits when its prefill finishes — better "
                         "short-prompt TTFT under mixed bursts)")
    ap.add_argument("--admission-lookahead", type=int, default=64,
                    help="queue reorder window for non-FIFO admission")
    ap.add_argument("--spec-park", type=int, default=0,
                    help=">0: park speculation + signal capture after N "
                         "consecutive gated-off rounds; resume via "
                         "periodic forced-speculation acceptance probes")
    ap.add_argument("--spec-probe-interval", type=int, default=8,
                    help="parked dispatches between acceptance probes")
    ap.add_argument("--trainer-threads", type=int, default=0,
                    help=">0: bound the async trainer's host-thread "
                         "contention with serving by deprioritizing the "
                         "training thread at the OS scheduler (the "
                         "in-process XLA pool is shared, so a hard "
                         "per-client thread cap needs the out-of-"
                         "process trainer — see ROADMAP)")
    # ---- disaggregation (repro/fleet; docs/disaggregation.md): not
    #      ServingConfig knobs — they select process/fleet topology
    #      around unchanged engines (FleetConfig; asserted total by
    #      tests/test_config_mirror.py)
    ap.add_argument("--fleet-replicas", type=int, default=0,
                    help=">0: serve through a data-parallel fleet of N "
                         "engine replicas behind a front-end router, "
                         "fed by one shared trainer over the draft-"
                         "version bus (0 = single engine)")
    ap.add_argument("--trainer-endpoint", default=None,
                    metavar="ENDPOINT",
                    help="run draft training out of process on its own "
                         "XLA client: 'spawn' forks a private trainer "
                         "subprocess; unix:/path or tcp:host:port "
                         "connect to a running "
                         "`python -m repro.fleet.trainer_main`")
    ap.add_argument("--fleet-route", choices=["least", "rr"],
                    default="least",
                    help="fleet request routing: least (cost-estimate "
                         "least-loaded, default) or rr (round-robin)")
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--shape", default="decode_32k")
    # ---- observability (repro/obs): main()-consumed, not ServingConfig
    #      knobs — the engine takes built tracer/recorder collaborators
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace-event JSON of "
                         "the run's host-side spans (superstep dispatch/"
                         "unpack, prefill chunks, train cycles, deploys) "
                         "to PATH at exit; chrome://tracing or ui."
                         "perfetto.dev loads it")
    ap.add_argument("--metrics-interval", type=float, default=0.0,
                    metavar="N",
                    help=">0: print a Prometheus-text metrics snapshot "
                         "(serving.*/train.*/paging.*/spec.* registry) "
                         "every N seconds from a background thread")
    ap.add_argument("--flight-record", action="store_true",
                    help="enable the per-request flight recorder and "
                         "print a timeline digest for the slowest "
                         "requests at exit")
    return ap


def config_from_args(args):
    """Assemble the ``ServingConfig`` the parsed flags name (the
    testable flag → config-field mapping; ``completion_sink`` is the
    one field with no flag — it is a host callback, not a knob)."""
    from repro.serving.policy import ServingConfig

    continuous = (getattr(args, "continuous", False) or args.gate_arrivals
                  or args.policy != "fifo")
    reseed = args.reseed_window
    if reseed is None:
        reseed = 32 if getattr(args, "async_train", False) else 0
    return ServingConfig(
        gamma=args.gamma, batch_size=args.batch,
        max_len=args.max_len or (160 if continuous else 96),
        greedy=not args.sample,
        superstep_rounds=args.superstep_rounds,
        eos_id=args.eos_id, ema=args.accept_ema, seed=args.seed,
        admission=args.policy, commit=args.commit,
        admission_lookahead=args.admission_lookahead,
        preempt=args.preempt, shed=args.shed,
        shed_queue_depth=args.shed_queue_depth,
        gate_arrivals=args.gate_arrivals, idle_wait_s=args.idle_wait_s,
        prefill_chunk=args.prefill_chunk,
        page_size=args.page_size, num_pages=args.num_pages,
        share_prefix=not args.no_share_prefix,
        spec_park_patience=args.spec_park,
        spec_probe_interval=args.spec_probe_interval,
        reseed_window=reseed, trainer_threads=args.trainer_threads,
        tree_width=args.tree_width)


def fleet_config_from_args(args):
    """Assemble the ``FleetConfig`` the disaggregation flags name (the
    testable flag → config-field mapping, same contract as
    ``config_from_args``).  Returns None when no fleet/remote-trainer
    topology was requested."""
    from repro.fleet import FleetConfig

    if not getattr(args, "fleet_replicas", 0) \
            and getattr(args, "trainer_endpoint", None) is None:
        return None
    return FleetConfig(replicas=args.fleet_replicas,
                       trainer_endpoint=args.trainer_endpoint,
                       route=args.fleet_route)


def main():
    args = build_parser().parse_args()

    if args.dryrun:
        import os
        import subprocess
        import sys
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch",
               args.arch, "--shape", args.shape]
        raise SystemExit(subprocess.call(cmd, env=dict(
            os.environ, PYTHONPATH=os.environ.get("PYTHONPATH", "src"))))

    import jax
    import numpy as np

    import repro.configs as configs
    from repro.core.adaptive import analytic_tpu_profile
    from repro.launch.compile_cache import enable_compile_cache
    from repro.core.tide import TideConfig, TideSystem
    from repro.data.workloads import (Phase, WorkloadStream, arrival_trace,
                                      make_domains, training_corpus)
    from repro.models import transformer as T
    from repro.training.trainer import pretrain_target

    enable_compile_cache()
    cfg = configs.get(args.arch) if args.arch == "tide-tiny" \
        else configs.get_reduced(args.arch)
    if cfg.family in ("audio", "vlm"):
        raise SystemExit(f"live demo serves text-only archs; {cfg.family} "
                         "frontends are stubbed (use --dryrun)")
    print(f"serving {cfg.name} ({cfg.param_count()/1e6:.2f}M params)")
    params = T.init(cfg, jax.random.key(0))

    domains = make_domains(cfg.vocab_size, ["science", "code"],
                           branchings=[2, 3], seed=3)
    corpus = np.concatenate([
        training_corpus(domains["science"], 64, 48, 1),
        training_corpus(domains["code"], 64, 48, 2)])
    print(f"pretraining target {args.pretrain_steps} steps...")
    params, losses = pretrain_target(cfg, params, corpus,
                                     steps=args.pretrain_steps, lr=3e-3)
    print(f"  loss {losses[0]:.2f} -> {losses[-1]:.2f}")

    n = args.requests
    args.continuous = (args.continuous or args.gate_arrivals
                       or args.policy != "fifo")
    scfg = config_from_args(args)
    from repro.obs import ObsConfig
    obs = ObsConfig(trace=args.trace_out is not None,
                    trace_path=args.trace_out,
                    record=args.flight_record)
    tc = TideConfig(serving=scfg,
                    n_threshold=4, signal_window=16,
                    adaptive_spec=not args.no_adaptive,
                    async_train=args.async_train,
                    obs=obs, fleet=fleet_config_from_args(args))
    profile = analytic_tpu_profile(cfg, chips=1)
    if tc.fleet is not None and tc.fleet.replicas > 0:
        return _main_fleet(args, cfg, params, tc, profile, domains)
    sys_ = TideSystem(cfg, params, tc, profile=profile)
    stop_metrics = _start_metrics_printer(sys_, args.metrics_interval)
    t0 = time.perf_counter()
    if args.continuous:
        # ragged budgets never exceed the user's --max-new-tokens cap
        mx = max(args.max_new_tokens, 1)
        # non-FIFO policies need SLO-annotated traces: a bimodal
        # loose/tight deadline mix for EDF, random priority classes
        slo = {}
        if args.policy == "deadline":
            slo = dict(deadline_slack=(8.0, 16.0), tight_frac=0.3,
                       tight_slack=(0.5, 2.0))
        elif args.policy == "priority":
            slo = dict(priority_levels=3)
        trace = arrival_trace(
            domains, n, mode="poisson", rate=16.0,
            max_new_range=(min(8, mx), mx),
            schedule=[Phase("science", n // 2), Phase("code", n - n // 2)],
            seed=1, **slo)
        sys_.run_stream(sys_.requests_from_trace(trace))
    else:
        stream = WorkloadStream(domains, [Phase("science", n // 2),
                                          Phase("code", n - n // 2)],
                                seed=1)
        sys_.run(stream.batches(args.batch),
                 max_new_tokens=args.max_new_tokens)
    if args.async_train:
        # finish any training the stream's signals still owe, then stop
        # the service thread cleanly
        sys_.service.drain()
        sys_.close()
    stop_metrics()
    s = sys_.summary()
    print(f"\n== TIDE summary ({time.perf_counter()-t0:.1f}s wall) ==")
    for k, v in s.items():
        print(f"  {k}: {v:.3f}" if isinstance(v, float) else f"  {k}: {v}")
    if args.async_train:
        print(f"  service: {sys_.service.stats()}")
    tl = sys_.engine.stats.timeline
    q = max(len(tl) // 4, 1)
    first = np.mean([x["accept_len"] for x in tl[:q]])
    last = np.mean([x["accept_len"] for x in tl[-q:]])
    print(f"  accept_len trend: {first:.2f} -> {last:.2f} "
          f"(draft adapted online, paper Fig. 5)")
    if args.trace_out:
        doc = sys_.export_trace()
        print(f"  trace: {len(doc['traceEvents'])} events -> "
              f"{args.trace_out}")
    if args.flight_record:
        _print_flight_digest(sys_.recorder)
    _exit_on_trainer_failures(s, sys_.service)


def _main_fleet(args, cfg, params, tc, profile, domains):
    """Fleet serving path (--fleet-replicas N): route an arrival trace
    across N data-parallel replicas fed by one shared (optionally
    out-of-process) trainer, and print the aggregate fleet summary."""
    import time as _time

    from repro.data.workloads import Phase, arrival_trace
    from repro.fleet.router import ServingFleet
    from repro.serving.request import Request

    n = args.requests
    mx = max(args.max_new_tokens, 1)
    trace = arrival_trace(
        domains, n, mode="poisson", rate=16.0,
        max_new_range=(min(8, mx), mx),
        schedule=[Phase("science", n // 2), Phase("code", n - n // 2)],
        seed=1)
    reqs = [Request(prompt=ev.prompt, domain=ev.domain,
                    max_new_tokens=ev.max_new_tokens, arrives_at=ev.t)
            for ev in trace]
    fleet = ServingFleet(cfg, params, tc, profile=profile)
    t0 = _time.perf_counter()
    fleet.serve(reqs)
    fleet.service.drain()
    fleet.close()
    s = fleet.summary()
    print(f"\n== fleet summary ({_time.perf_counter()-t0:.1f}s wall, "
          f"{s['replicas']} replicas) ==")
    for k, v in s.items():
        print(f"  {k}: {v:.3f}" if isinstance(v, float) else f"  {k}: {v}")
    _exit_on_trainer_failures(s, fleet.service)


def _exit_on_trainer_failures(summary, service):
    """The training service keeps serving through a failed train
    cycle; the launcher does not pass such a run as a success."""
    n = summary["trainer_failures"]
    if n:
        print(f"  last trainer error: {service.last_error}")
        raise SystemExit(f"draft training failed {n} time(s)")


def _start_metrics_printer(sys_, interval: float):
    """Background Prometheus-text snapshot printer (--metrics-interval).
    Reads only host-side registry state — callback gauges and counters —
    so it never perturbs serving.  Returns a stop() callable."""
    if interval <= 0:
        return lambda: None
    import threading
    stop = threading.Event()

    def loop():
        while not stop.wait(interval):
            print(f"\n-- metrics @{time.strftime('%H:%M:%S')} --")
            print(sys_.metrics.to_prometheus(), end="")

    t = threading.Thread(target=loop, name="tide-metrics", daemon=True)
    t.start()

    def stop_fn():
        stop.set()
        t.join(timeout=5.0)

    return stop_fn


def _print_flight_digest(recorder, worst: int = 3):
    """Per-request flight-recorder digest: the ``worst`` highest-latency
    completed requests, with their event timelines."""
    tls = sorted(recorder.timelines(),
                 key=lambda tl: tl.get("latency_s") or 0.0, reverse=True)
    print(f"\n== flight recorder ({len(tls)} requests) ==")
    for tl in tls[:worst]:
        print(f"  rid={tl['rid']} sid={tl['sid']} domain={tl['domain']} "
              f"ttft={tl.get('ttft_s')} latency={tl.get('latency_s')}")
        for ev in tl["events"]:
            extra = {k: v for k, v in ev.items()
                     if k not in ("kind", "round", "t")}
            print(f"    r{ev['round']:>5} t={ev['t']:.3f}s {ev['kind']}"
                  + (f" {extra}" if extra else ""))


if __name__ == "__main__":
    main()
