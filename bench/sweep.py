"""Find a cell's knee once, by a sweep of fixed open-loop rates on the
chip, in one process (the comparison against the reference is skipped).

  python3 bench/sweep.py --workload sc2-7b.chat --rates 3 4 5 6 \\
      --seconds 20 --seed 9

For each rate it prints the offered and delivered tokens/s, the 90th
percentiles of TTFT, queue wait and time per output token, and how many
of the window's requests were still queued at its close.  The knee is
the highest rate whose delivered tokens keep up with the offered ones
and whose queue does not grow through the window.
"""
import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from bench import cell
    from bench.run_cell import enable_cache
    enable_cache(ROOT)
    for i, rate in enumerate(args.rates):
        res = cell.run(args.workload, args.seed + i, args.seconds, False,
                       t_proc=time.perf_counter(), root=ROOT, rate=rate,
                       reference=False)
        m = {k: v["value"] for k, v in res.line["metrics"].items()}
        print(json.dumps({"rate_rps": rate, **m,
                          "offered_tok_s": res.notes["offered_tok_s"],
                          "queue_wait_p90_s": res.notes["queue_wait_p90_s"],
                          "queued_at_close": res.notes["queued_at_close"],
                          "train_cycles_window":
                              res.notes["train_cycles_window"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
