"""The control of `correct`: the reference put in the program's place and
computed one precision below the configuration's (4-bit LUT weights for
its 8-bit ones), compared exactly as a run compares the program.  It has
to come out not correct.  Not part of a benchmark run.

    python3 bench/control.py --workload bnlearn.long --seeds 11 12 13

For each seed it answers the first `check_queries` queries of the window
stream with the control and with the reference and prints one JSON line:
the mismatched queries out of those checked.
"""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# int4 weights stand in for int8: the next precision below the stated one
CONTROL_WEIGHT_BITS = 4


def control_mismatches(root: pathlib.Path, workload: str, seed: int) -> dict:
    from bench import check, generator, models, spec

    cell = spec.load_cell(root, workload)
    config, traffic = cell.config, cell.traffic
    plain = models.build(config)
    stream = generator.Stream(traffic, config, plain, seed, generator.WINDOW)
    queries = []
    while len(queries) < traffic["check_queries"]:
        queries += stream.round()
    queries = queries[:traffic["check_queries"]]
    control = check.Reference(config, traffic, plain, CONTROL_WEIGHT_BITS)
    reference = check.Reference(config, traffic, plain)
    served = [(q, control.answer(q)) for q in queries]
    return {"workload": workload, "seed": seed,
            "mismatched_queries": check.mismatches(reference, served),
            "checked_queries": len(queries)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import harness
    from repro.core import compat

    try:
        harness.device_info(1, require_tpu=True)
    except harness.NoAccelerator as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 1
    compat.setup_compile_cache()
    for seed in args.seeds:
        print(json.dumps(control_mismatches(ROOT, args.workload, seed)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
