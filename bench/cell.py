"""One run of one cell: set up, warm every shape, measure a window of
open-loop traffic, check what was served against the plain reference,
and report.

Timeline of a run (host ``perf_counter`` clock throughout):

1. set-up: weights made on the chip from the seed; every refill shape
   the mix can produce compiled (or loaded from the persistent cache)
   by bursts of requests; the draft trainer's step and eval programs
   compiled; adaptation state reset;
2. one request stream through ``TideSystem.run_stream``: ``warm_s`` of
   the mix's traffic (lanes fill, at least one train cycle runs), then
   the measured window of ``seconds``, then no more arrivals until every
   request is served;
3. after the stream: peak memory read, the program's state freed, the
   reference run over a seeded sample of the window's requests, over
   the signal windows the stream's first draft train steps consumed,
   and through those steps (``bench/check.py``).

The window starts at the scheduled send time of the first measured
request slot (``warm_s`` after the stream starts) and lasts ``seconds``;
end-to-end metrics are taken from the requests whose scheduled send lies
in it, and from counter snapshots a sampler thread takes at its edges.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import logging
import os
import shutil
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from bench import arrivals, check, devtrace, peaks

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
WARM_BUDGET = 2          # tokens per warm-up burst request
TRACE_S = 8.0            # traced part of the window, at most
PRIMER_LEAD_S = 0.05     # a warm burst arrives this long after its primer
TRAIN_STEPS = 3          # first draft train steps compared
# training knobs a mix's ``serving`` block may set (TideConfig's otherwise)
TRAIN_KEYS = ("signal_window", "n_threshold", "train_min_steps")
_STAT_KEYS = ("tokens_out", "steps", "accept_len_sum", "accept_len_n",
              "prefill_row_tokens", "busy_lane_rounds", "dispatches",
              "refills")


class CellError(RuntimeError):
    """The cell cannot run here (no chip, unknown name, bad file)."""


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------ data files
def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bm: dict, name: str):
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    return cell, cfg


def load_config(entry: dict, root: str = ROOT) -> dict:
    with open(os.path.join(root, entry["file"])) as f:
        doc = json.load(f)
    doc["name"] = entry["name"]
    return doc


def reference_module(doc: dict):
    return importlib.import_module("bench.configs." + doc["reference"])


def serving_settings(doc: dict, mix: dict) -> dict:
    s = dict(mix["serving"])
    gamma = s["gamma"]
    longest = max(mix["prompt"]["widths"]) + mix["output"]["max"] \
        + 2 * (gamma + 1)
    s["max_len"] = -(-longest // 8) * 8
    return s


# -------------------------------------------------------------- counters
def snapshot(sys_) -> dict:
    st = sys_.engine.stats
    snap = {k: getattr(st, k) for k in _STAT_KEYS}
    snap["train_cycles"] = sum(e["kind"] == "train_cycle"
                               for e in sys_.service.events)
    snap["t"] = time.perf_counter()
    return snap


class Sampler(threading.Thread):
    """Takes counter snapshots at the window's edges, and starts and stops
    the profiler around the traced part, by sleeping on the host clock
    from the moment the stream is started."""

    def __init__(self, sys_, t_start, warm_s, seconds, trace_dir):
        super().__init__(name="bench-sampler", daemon=True)
        self.sys_ = sys_
        self.edges = {"w0": t_start + warm_s, "w1": t_start + warm_s
                      + seconds}
        self.trace_dir = trace_dir
        if trace_dir:
            self.edges["tr0"] = self.edges["w1"] - min(TRACE_S, seconds)
        self.snaps: Dict[str, dict] = {}
        self.late: Dict[str, float] = {}
        self.error: Optional[str] = None
        self.trace_t0: Optional[float] = None    # host time tracing began
        self.stop_trace_s: Optional[float] = None
        self._halt = threading.Event()

    def _at(self, key) -> bool:
        wait = self.edges[key] - time.perf_counter()
        if wait > 0 and self._halt.wait(wait):
            return False
        self.late[key] = time.perf_counter() - self.edges[key]
        return True

    def run(self):
        import jax
        tracing = False
        try:
            if not self._at("w0"):
                return
            self.snaps["w0"] = snapshot(self.sys_)
            if self.trace_dir:
                # the traced part closes the window, so the profiler's
                # stop, which takes seconds, falls after it
                if not self._at("tr0"):
                    return
                self.trace_t0 = time.perf_counter()
                jax.profiler.start_trace(self.trace_dir)
                tracing = True
                self.snaps["tr0"] = snapshot(self.sys_)
            if self._at("w1"):
                self.snaps["w1"] = snapshot(self.sys_)
                if tracing:
                    self.snaps["tr1"] = self.snaps["w1"]
        except Exception as exc:       # reported; the run is then refused
            self.error = f"{type(exc).__name__}: {exc}"
        finally:
            if tracing:
                t = time.perf_counter()
                jax.profiler.stop_trace()
                self.stop_trace_s = time.perf_counter() - t

    def finish(self, halt: bool = False):
        """Wait for the remaining edges (a stream can end before the
        window does) unless ``halt``, then for the thread."""
        if halt:
            self._halt.set()
        self.join(timeout=max(self.edges["w1"] - time.perf_counter(), 0)
                  + 300)
        self._halt.set()


class CompileLog(logging.Handler):
    """Names of the programs JAX compiles, with the host time of each
    (from ``jax_log_compiles``), kept off standard error."""

    LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")

    def __init__(self):
        super().__init__(logging.WARNING)
        import jax
        jax.config.update("jax_log_compiles", True)
        self.records: List[tuple] = []
        for name in self.LOGGERS:
            lg = logging.getLogger(name)
            lg.addHandler(self)
            lg.propagate = False

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith(("Compiling", "Finished tracing")):
            self.records.append((time.perf_counter(), msg[:200]))

    def close(self):
        import jax
        jax.config.update("jax_log_compiles", False)
        for name in self.LOGGERS:
            lg = logging.getLogger(name)
            lg.removeHandler(self)
            lg.propagate = True
        super().close()


def _norms(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        tree)


def _change_norms(new, old):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), new, old)


def _by_path(tree) -> Dict[str, float]:
    import jax

    from bench import weights
    return {weights.path_str(p): float(v) for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


class StepRecorder:
    """Stands in for the draft trainer's compiled step and passes every
    call through.  Of the first ``TRAIN_STEPS`` calls after it is put in
    place (the first steps of the stream's first train cycle) it keeps
    the windows fed, the loss returned, the per-leaf norms of the first
    moment after the first step, and of the draft's change from the
    first step's input to the last step's output: device arrays all,
    read only once the window has closed."""

    def __init__(self):
        import jax
        self.step = None
        self.norms = jax.jit(_norms)
        self.change_norms = jax.jit(_change_norms)
        self.feats: List = []
        self.tokens: List = []
        self.losses: List = []
        self.moment = None
        self.change = None
        self._p0 = None

    def install(self, trainer):
        self.step = trainer._step
        trainer._step = self

    def warm(self, dparams, out):
        """Compile the two norm programs on a step's output."""
        import jax
        jax.block_until_ready((self.norms(out[1]["m"]),
                               self.change_norms(out[0], dparams)))

    def __call__(self, dparams, opt_state, embed, feats, tokens, it):
        out = self.step(dparams, opt_state, embed, feats, tokens, it)
        k = len(self.losses)
        if k < TRAIN_STEPS:
            self.feats.append(feats)
            self.tokens.append(tokens)
            self.losses.append(out[2])
            if k == 0:
                self._p0 = dparams
                self.moment = self.norms(out[1]["m"])
            if k == TRAIN_STEPS - 1:
                self.change = self.change_norms(out[0], self._p0)
                self._p0 = None
        return out

    def readings(self, beta1: float) -> Optional[dict]:
        """The program's side of the train-step comparison, on the host;
        None unless all the steps ran."""
        if self.change is None:
            return None
        return {"feats": [np.asarray(f) for f in self.feats],
                "tokens": [np.asarray(t) for t in self.tokens],
                "losses": [float(x) for x in self.losses],
                "grad": {k: v / (1.0 - beta1)
                         for k, v in _by_path(self.moment).items()},
                "change": _by_path(self.change)}


# ---------------------------------------------------------------- system
def build_system(doc: dict, mix: dict, seed: int):
    """Weights from the seed on the first chip, and the TIDE system the
    cell serves with."""
    import jax

    from repro.core import eagle
    from repro.core.tide import TideConfig, TideSystem
    from repro.models import transformer as T

    from bench import weights
    cfg = reference_module(doc).program_config(doc)
    dcfg = eagle.draft_config(cfg)
    g = doc["draft"]
    got = {"num_hidden_layers": dcfg.num_layers,
           "hidden_size": dcfg.d_model,
           "num_attention_heads": dcfg.num_heads,
           "num_key_value_heads": dcfg.num_kv_heads,
           "head_dim": dcfg.head_dim, "intermediate_size": dcfg.d_ff,
           "fuse_inputs": 3}
    got["capture_layers"] = list(cfg.captures)
    if got != g:
        raise CellError(f"the program's draft {got} is not the draft the "
                        f"configuration file states {g}")
    key = jax.random.key(0)
    params = weights.make(jax.eval_shape(lambda: T.init(cfg, key)),
                          seed, "target")
    dparams = weights.make(jax.eval_shape(
        lambda: eagle.draft_init(dcfg, key)), seed, "draft")
    params, dparams = jax.device_put((params, dparams), jax.devices()[0])
    jax.block_until_ready((params, dparams))
    s = serving_settings(doc, mix)
    tcfg = TideConfig(
        gamma=s["gamma"], batch_size=s["batch_size"], max_len=s["max_len"],
        superstep_rounds=s["superstep_rounds"],
        prefill_chunk=s["prefill_chunk"], adaptive_spec=s["adaptive_spec"],
        async_train=s["async_train"],
        selective_training=s["selective_training"], gate_arrivals=True,
        seed=seed % (2 ** 31),
        **{k: s[k] for k in TRAIN_KEYS if k in s})
    return cfg, TideSystem(cfg, params, tcfg, dparams=dparams), s


def _request(send: arrivals.Send, arrives: Optional[float]):
    from repro.serving.request import Request
    return Request(prompt=send.prompt.tolist(), domain=send.domain,
                   max_new_tokens=send.max_new, arrives_at=arrives)


def warm_shapes(sys_, cfg, mix: dict, settings: dict, seed: int,
                recorder: Optional[StepRecorder] = None):
    """Compile (or load) every program the window can call: the chunked
    refill programs for each width x row bucket, solo and in cohorts,
    the superstep, and the draft trainer's step and eval programs; then
    reset the adaptation state the bursts left."""
    import jax
    import jax.numpy as jnp

    domains = arrivals.make_domains(cfg.vocab_size, mix)
    dom = domains[next(iter(domains))]
    rng = np.random.default_rng([seed, 3])
    widths = mix["prompt"]["widths"]
    for burst in arrivals.warm_bursts(widths, settings["batch_size"]):
        # a stream's first refill runs on the engine's fresh (uncommitted)
        # state and its later ones on committed state, which JAX compiles
        # apart: a primer takes the first, the burst the second
        primer = dom.sample(rng, [min(widths)])[0]
        reqs = [_request(arrivals.Send(0.0, "warm", primer, WARM_BUDGET),
                         0.0)]
        for width, n in burst.items():
            for toks in dom.sample(rng, [width] * n):
                reqs.append(_request(arrivals.Send(
                    0.0, "warm", toks, WARM_BUDGET), PRIMER_LEAD_S))
        sys_.run_stream(reqs)
    tr = sys_.trainer
    if tr is not None:
        dparams, _ = sys_.gate.current()
        win = sys_.tcfg.signal_window
        feats = 3 * cfg.d_model
        opt = tr.opt.init(dparams)
        out = tr._step(dparams, opt, tr.embed_params,
                       jnp.zeros((tr.batch_size, win, feats), cfg.act_dtype),
                       jnp.zeros((tr.batch_size, win), jnp.int32),
                       jnp.int32(0))
        jax.block_until_ready(out)
        if recorder is not None:
            recorder.warm(dparams, out)
        del out, opt
        # a cycle holds out a tenth of the windows it drains; a drain
        # takes 96 windows and the few one superstep adds
        lo = sys_.tcfg.n_threshold // 10
        for k in range(max(lo - 1, 1), lo + 8):
            jax.block_until_ready(tr._eval(
                dparams, tr.embed_params,
                jnp.zeros((k, win, feats), cfg.act_dtype),
                jnp.zeros((k, win), jnp.int32)))
    sys_.reset_adaptation()


# ------------------------------------------------------------------ run
@dataclasses.dataclass
class Result:
    line: dict      # the result line; ``line["checks"]`` the compared numbers
    notes: dict     # what earlier lines of the run report


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs, float), q)) if xs else None


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_proc: float, root: str = ROOT, require_tpu: bool = True,
        bench: Optional[dict] = None, mix: Optional[dict] = None,
        rate: Optional[float] = None,
        control: bool = False, fault=None,
        reference: bool = True) -> Result:
    """One run of ``cell_name``.  ``bench``/``mix`` override the files
    (tests); ``rate`` overrides the mix's rate (the knee sweep);
    ``control`` puts the control's products in the program's place in
    the comparison (``bench/control.py``); ``fault(sys_)`` plants a
    fault in the timed path (the fault tests); ``reference=False`` skips
    the comparison (the knee sweep reads no ``correct``)."""
    import jax

    bench = bench or load_benchmark(root)
    cell, cfg_entry = find_cell(bench, cell_name)
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise CellError(f"no TPU: JAX found {devs[0].platform}; this "
                            "benchmark measures the chip and has no CPU "
                            "mode")
        if len(devs) < cell["chips"]:
            raise CellError(f"{cell_name} needs {cell['chips']} chips, "
                            f"JAX found {len(devs)}")
    kind = devs[0].device_kind
    peak = peaks.peaks(kind) if require_tpu else peaks.PEAKS["TPU v5 lite"]
    doc = load_config(cfg_entry, root)
    mix = dict(mix or arrivals.load_mix(cell["traffic"]))
    if rate is not None:
        mix["rate_rps"] = rate

    compiles: List[tuple] = []
    phases = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/backend_compile_duration": "compile"}

    def on_duration(event, secs, **_):
        if event in phases:
            compiles.append((time.perf_counter(), phases[event], secs))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    named = CompileLog()

    t_jax = time.perf_counter()
    cfg, sys_, settings = build_system(doc, mix, seed)
    t_weights = time.perf_counter()
    recorder = (StepRecorder() if reference and sys_.trainer is not None
                else None)
    warm_shapes(sys_, cfg, mix, settings, seed, recorder)
    if fault is not None:
        fault(sys_)
    if recorder is not None:
        recorder.install(sys_.trainer)
    t_warm = time.perf_counter()
    warm_s = float(mix["warm_s"])
    domains = arrivals.make_domains(cfg.vocab_size, mix)
    warm = arrivals.schedule(mix, cfg.vocab_size, seed, warm_s,
                             phase="warm", domains=domains)
    window = arrivals.schedule(mix, cfg.vocab_size, seed, seconds,
                               phase="window", start=warm_s,
                               domains=domains)
    reqs = [_request(s, s.t) for s in warm + window]
    measured = reqs[len(warm):]
    trace_dir = None
    if trace:
        trace_dir = os.path.join(root, ".bench_trace", str(os.getpid()))
        shutil.rmtree(trace_dir, ignore_errors=True)
    sampler = Sampler(sys_, time.perf_counter(), warm_s, seconds, trace_dir)
    sampler.start()
    try:
        sys_.run_stream(reqs)
    except BaseException:
        sampler.finish(halt=True)
        raise
    t_done = time.perf_counter()
    sampler.finish()
    summary = sys_.summary()
    svc = sys_.service
    t0 = reqs[0].arrival_t - reqs[0].arrives_at     # scheduler's origin
    w0, w1 = t0 + warm_s, t0 + warm_s + seconds
    snaps = sampler.snaps
    rows = [{"send": t0 + r.arrives_at, "admit": r.admit_t,
             "first": r.first_token_t, "finish": r.finish_t,
             "n": len(r.generated), "budget": r.max_new_tokens,
             "width": len(r.prompt)} for r in measured]
    mem = devs[0].memory_stats() or {}
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devs[:cell["chips"]])
    served = [(np.asarray(r.prompt), np.asarray(r.generated))
              for r in measured]
    # every sequence of the stream, where the trained windows are found
    streamed = [np.concatenate([r.prompt, r.generated]).astype(np.int32)
                for r in reqs]
    steps = (recorder.readings(doc["draft_training"]["beta1"])
             if recorder is not None else None)
    del recorder
    vocab = cfg.vocab_size
    sys_.close()
    trace_t0, stop_trace_s = sampler.trace_t0, sampler.stop_trace_s
    sampler.sys_ = None
    del sys_, reqs, warm, window, measured
    gc.collect()
    if sampler.error or not all(k in snaps for k in (
            ("w0", "w1") + (("tr0", "tr1") if trace else ()))):
        raise CellError(f"window sampler failed: {sampler.error}; took "
                        f"{sorted(snaps)}")

    # ---------------------------------------------------- correctness
    failed = [i for i, r in enumerate(rows)
              if r["finish"] is None or r["n"] != r["budget"]]
    bad_tok = sum(int(((g < 0) | (g >= vocab)).any()) for _, g in served)
    ref = reference_module(doc)
    sample = check.sample(rows, seed) if reference else []
    t_ref = time.perf_counter()
    gaps = check.served_gaps(ref, doc, seed, [served[i] for i in sample],
                             settings["max_len"], control=control)
    train = (check.train_step(ref, doc, seed, steps, streamed,
                              settings["max_len"], control)
             if steps is not None else None)
    t_ref = time.perf_counter() - t_ref
    del streamed
    # the control's products take the program's place in the comparison
    side = "control" if control else "program"
    # a mix whose requests are too short to fill a train cycle in a run
    # says so with ``"trains": false``; every other stream has to train
    trains = bool(mix.get("trains", True))
    got = check.merge(gaps, train, side, trains)
    checks = {
        "failed_requests": {"value": len(failed), "limit": 0},
        "tokens_out_of_vocab": {"value": bad_tok, "limit": 0},
        "trainer_failures": {"value": svc.failures, "limit": 0},
    }
    for k, v in got.items():
        checks[k] = {"value": v, "limit": doc["check"][k]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    # ------------------------------------------------------- metrics
    ttft = [r["first"] - r["send"] if r["first"] is not None
            else float("inf") for r in rows]
    tpot = [1000.0 * (r["finish"] - r["first"]) / (r["n"] - 1)
            for r in rows if r["finish"] is not None and r["n"] > 1]
    e2e = {}
    if "w0" in snaps and "w1" in snaps:
        e2e["output_tok_s"] = (snaps["w1"]["tokens_out"]
                               - snaps["w0"]["tokens_out"]) \
            / (snaps["w1"]["t"] - snaps["w0"]["t"])
    e2e["ttft_p90_s"] = _pct(ttft, 90)
    e2e["tpot_p90_ms"] = _pct(tpot, 90)
    e2e["setup_s"] = w0 - t_proc
    in_window = [c for c in compiles if w0 <= c[0] <= w1]
    named.close()
    notes = {
        "requests_in_window": len(rows), "served_tokens_compared":
        gaps["tokens"], "sampled_requests": len(sample),
        "ref_logit_rms": gaps["ref_rms"],
        "compiles_in_window": sum(c[1] == "compile" for c in in_window),
        "traces_in_window": sum(c[1] == "trace" for c in in_window),
        "compiled_in_window": sorted({m for t, m in named.records
                                      if w0 <= t <= w1}),
        "compile_s_total": sum(c[2] for c in compiles if c[1] == "compile"),
        "sampler_late_s": max(sampler.late.values(), default=None),
        "start_s": t_jax - t_proc, "system_s": t_weights - t_jax,
        "warm_shapes_s": t_warm - t_weights, "reference_s": t_ref,
        "drain_s": t_done - w1, "peak_bytes_in_use": peak_bytes,
        "bytes_limit": mem.get("bytes_limit"),
        "train_cycles_window": (snaps["w1"]["train_cycles"]
                                - snaps["w0"]["train_cycles"])
        if "w1" in snaps and "w0" in snaps else None,
        "train_cycles_before_window": snaps.get("w0", {}).get(
            "train_cycles"),
        "train_cycles": summary["train_cycles"],
        "deploys": summary["deploys"],
        "signals_dropped": summary["signals_dropped"],
        "accept_len_run": summary["accept_len"],
        "offered_tok_s": sum(r["budget"] for r in rows) / seconds,
        "tpot_p90_ms": e2e["tpot_p90_ms"],
        "queue_wait_p90_s": _pct([r["admit"] - r["send"] for r in rows
                                  if r["admit"] is not None], 90),
        "queued_at_close": sum(r["admit"] is None or r["admit"] > w1
                               for r in rows),
    }
    if train is not None:
        notes["train_windows"] = train["windows"]
        notes["train_windows_unlocated"] = train["unlocated"]
        notes["train_losses"] = steps["losses"]
        notes["train_ref_losses"] = train.get("ref_losses")
        notes["train_still_leaves"] = train.get("program", {}).get(
            "still_leaves")
    if control:
        # the program's own readings, beside the control's in ``checks``
        notes["program_readings"] = check.merge(gaps, train, "program",
                                                trains)

    device = {"platform": devs[0].platform, "kind": kind,
              "count": cell["chips"], "memory_peak_bytes": peak_bytes}
    line = {"correct": bool(correct), "attempted": len(rows),
            "failed": len(failed)}
    wanted = [m for m in bench["end_to_end"]
              if cell_name in m.get("workloads", [cell_name])]
    breakdown = None
    if trace:
        ctx = {"doc": doc, "mix": mix, "peaks": peak,
               "gamma": settings["gamma"], "requests": rows,
               "window": (snaps.get("w0"), snaps.get("w1")),
               "traced": ((snaps["tr0"], snaps["tr1"])
                          if "tr1" in snaps else None),
               "trace": None}
        t_red = time.perf_counter()
        red = reduce_trace(trace_dir, trace_t0, snaps["tr0"]["t"],
                           snaps["tr1"]["t"])
        notes["trace_reduce_s"] = time.perf_counter() - t_red
        notes["stop_trace_s"] = stop_trace_s
        shutil.rmtree(os.path.dirname(trace_dir), ignore_errors=True)
        if red is not None:
            ctx["trace"] = red
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            notes["trace_modules"] = {k: [v["s"], v["calls"]] for k, v in
                                      sorted(red["modules"].items())}
            breakdown = {
                "device_ops": [[k, v["s"]] for k, v in sorted(
                    red["modules"].items(), key=lambda kv: -kv[1]["s"])
                    [:10]],
                "idle_gaps": red["gaps_top"]}
        metrics = {}
        for m in bench["per_layer"]:
            if cell_name not in m.get("workloads", [cell_name]):
                continue
            val = read_metric(m["name"], ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in wanted if e2e.get(m["name"]) is not None}
    line["metrics"] = metrics
    line["device"] = device
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return Result(line, notes)


def read_metric(name: str, ctx: dict):
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def reduce_trace(trace_dir: Optional[str], t_begin: float,
                 t0: float, t1: float):
    """Reduce the trace over host times [t0, t1].  The trace's clock is
    anchored at its ``start_trace`` event, which began at host time
    ``t_begin``; without that event, at the first event."""
    if not trace_dir:
        return None
    path = devtrace.find_xplane(trace_dir)
    if path is None:
        log("trace: no .xplane.pb written")
        return None
    planes = devtrace.planes_of(path)
    starts = [ev[1] for p in planes for ln in p["lines"]
              for ev in ln["events"]]
    if not starts:
        return None
    anchor = min(starts)
    for p in planes:
        for ln in p["lines"]:
            for name, s, _ in ln["events"]:
                if name.endswith(" start_trace"):
                    anchor = s
    ns = lambda t: anchor + (t - t_begin) * 1e9   # noqa: E731
    return devtrace.reduce(planes, (ns(t0), ns(t1)))
