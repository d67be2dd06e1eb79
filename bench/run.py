"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload bnlearn.long --seed 7 --seconds 30 --trace 0

Builds the cell's models and query stream from `BENCHMARK.json` and the
seed, warms every executable the window uses, serves the closed loop for
`--seconds`, checks a seeded sample of the answers against the plain
reference, and prints one JSON object as the last line of standard output.
With `--trace 1` it reports the cell's per-layer metrics, read from a
profiler trace of the window's first seconds, in place of the end-to-end
ones.  Without a TPU (or with fewer chips than the cell asks for) it exits
with code 1 and prints no result.  The persistent compilation cache lives
in `<checkout>/.jax_cache` unless `JAX_COMPILATION_CACHE_DIR` names one.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    import jax

    from bench import harness

    try:
        harness.device_info(1, require_tpu=True)
    except harness.NoAccelerator as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 1
    from repro.core import compat

    compat.setup_compile_cache()
    # cache every executable, however quickly it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        result = harness.run_cell(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
            T_START, trace_dir=args.trace_dir,
            log=lambda s: print(s, file=sys.stderr, flush=True))
    except harness.NoAccelerator as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
