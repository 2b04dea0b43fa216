"""Run one cell of ``BENCHMARK.json`` once, on the chip, and print its
result as the last line of standard output.

  python3 bench/run_cell.py --workload sc2-7b.chat --seed 7 \\
      --seconds 30 --trace 0

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
profiles the last seconds of the window and reports its per-layer
metrics.  Every compared number is printed beside its limit as the last
lines of standard error and under ``checks`` in the result.  With no
TPU, or fewer chips than the cell asks for, it exits 2 and prints no
result.  JAX's persistent compilation cache is kept at
``$JAX_COMPILATION_CACHE_DIR`` or, unset, at ``<checkout>/.jax_cache``.
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


def enable_cache(root: str) -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def report(res) -> None:
    for k, v in res.notes.items():
        print(f"note {k}: {v}", file=sys.stderr)
    for k, c in res.line["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res.line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import cell
    try:
        enable_cache(ROOT)
        res = cell.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_proc=T_PROC, root=ROOT)
    except cell.CellError as exc:
        print(f"run_cell: {exc}", file=sys.stderr)
        return 2
    report(res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
