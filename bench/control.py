"""Readings for the limits of ``correct``: the program's compared numbers
and the control's, on several seeds, at the cell's own size and load,
in one process (the benchmark's own runs never run the control).

  python3 bench/control.py --workload sc2-7b.chat --seconds 15 \\
      --seeds 11 12 13

The control is the plain reference computed with float8_e4m3 weights and
matmul inputs (the precision below the configuration's bfloat16), put in
the program's place: its own top token at each position of the same
served sequences, its own features of the same trained windows, and its
own first draft train steps on them.  Its readings go through the
run's own comparison, so its ``correct`` has to come out false.  With
``--fault <name>`` the program runs with that fault of ``bench/faults.py``
planted, and no control.  Prints one JSON line per seed.
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    from bench import cell, faults
    from bench.run_cell import enable_cache
    enable_cache(ROOT)
    for seed in args.seeds:
        res = cell.run(args.workload, seed, args.seconds, False,
                       t_proc=time.perf_counter(), root=ROOT,
                       control=args.fault is None,
                       fault=faults.FAULTS.get(args.fault))
        print(json.dumps({
            "seed": seed, "correct": res.line["correct"],
            "fault": args.fault,
            "program": res.notes.get("program_readings"),
            "control" if args.fault is None else "faulty": {k: c["value"]
                        for k, c in res.line["checks"].items()},
            "limits": {k: c["limit"]
                       for k, c in res.line["checks"].items()},
            "tokens": res.notes["served_tokens_compared"],
            "ref_logit_rms": res.notes["ref_logit_rms"],
            "train_losses": res.notes.get("train_losses"),
            "reference_s": res.notes["reference_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
