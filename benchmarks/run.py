"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and, when every suite ran,
writes the pass to ``benchmarks/results/BENCH_BASELINE.json`` — the
machine-readable perf+quality baseline: each PR's full run snapshots
every suite's rows, a sampling-quality sweep (``repro.diag`` at the CI
budget — split R-hat / ESS / TV-vs-exact per model and backend variant),
the git SHA it was measured at, and the backend and budget flags, so
later PRs can diff themselves against a recorded baseline
(``benchmarks/check_regression.py``) instead of folklore.  Partial
``--smoke``/``--only`` passes leave the baseline untouched.  Every
baseline write also appends a timestamped copy to
``benchmarks/results/trajectory/`` — the per-PR history the snapshots
overwrite.  ``--quick`` trims budgets; ``--fused`` routes the
bayesnet/compile suites through the fused Pallas kernels as well;
``--skip-quality`` omits the quality sweep; ``--roofline`` additionally
summarizes the dry-run roofline table (requires
benchmarks/results/dryrun/*.json from repro.launch.dryrun)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import (bench_bayesnet, bench_breakdown, bench_coloring,
                        bench_compile, bench_entropy, bench_interp,
                        bench_mrf, bench_runtime, bench_sampler,
                        bench_token_sampler)

SUITES = {
    "sampler": bench_sampler.run,          # Table II
    "interp": bench_interp.run,            # Table III
    "bayesnet": bench_bayesnet.run,        # Table IV
    "mrf": bench_mrf.run,                  # Fig. 12/13
    "entropy": bench_entropy.run,          # Fig. 11
    "coloring": bench_coloring.run,        # Fig. 9
    "breakdown": bench_breakdown.run,      # Fig. 2a
    "token_sampler": bench_token_sampler.run,  # beyond-paper (Table V ana.)
    "compile": bench_compile.run,          # compile chain (Sec. IV / Fig. 8)
    "runtime": bench_runtime.run,          # batched serving vs serial
}

# CI sanity set: fast, CPU-friendly, exercises the compile chain end to end
SMOKE_SUITES = ("coloring", "compile")

# suites that understand the --fused knob (the Pallas round kernels)
FUSED_SUITES = ("bayesnet", "compile")

BASELINE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "results",
    "BENCH_BASELINE.json",
)
TRAJECTORY_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "results", "trajectory",
)


def git_sha() -> str:
    """HEAD SHA of the repo the benchmarks live in, or "unknown" outside
    a checkout — stamped into every baseline so a trajectory entry names
    the exact code it measured."""
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        )
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else "unknown"
    except OSError:
        return "unknown"


def quality_rows(quick: bool) -> list[dict]:
    """The sampling-quality side of the baseline: the `repro.diag` sweep
    at the CI (--quick) budget — one row per (model, variant) with
    rhat_max / ess_min / tv_max — so the regression gate can diff quality
    alongside us_per_call.  Full (non-quick) benchmark passes still use
    the quick *quality* budget: the gate needs stable, cheap reference
    numbers, not the deepest possible audit."""
    from repro.diag.__main__ import (QUICK_BURN_IN, QUICK_N_ITERS,
                                     quality_sweep)

    report = quality_sweep(
        ("survey",) if quick else ("survey", "alarm"),
        n_iters=QUICK_N_ITERS,
        burn_in=QUICK_BURN_IN,
    )
    for f in report.findings:
        print(f"# quality finding: {f.render()}")
    return report.meta["rows"]


def profile_rows(quick: bool) -> list[dict]:
    """The static-cost side of the baseline: `repro.obs.profile`'s fixed
    model-zoo sweep — per-executable-signature flops / hbm_bytes /
    collective_bytes + roofline bottleneck, derived from the compiled
    artifacts at a tiny fixed budget.  Pure compile-time data (no wall
    clock), so the drift gate can re-derive and diff it bit-for-bit on
    the same jax version."""
    from repro.obs import profile as profile_mod

    return profile_mod.static_profile_sweep(quick=quick)


def parse_row(row: str) -> dict:
    """One ``name,us_per_call,derived`` CSV row -> a JSON-friendly record
    (``derived`` stays a raw string: its key=value grammar is per-suite)."""
    name, us, derived = row.split(",", 2)
    return {"name": name, "us_per_call": float(us), "derived": derived}


def write_baseline(suite_rows: dict, args) -> None:
    """Snapshot this pass as the machine-readable perf baseline.

    Refuses to overwrite a baseline measured under *different* budgets
    (quick vs full, fused on/off): diffing us_per_call across budget
    regimes is exactly the folklore this artifact exists to kill.  A
    mismatched pass lands in BENCH_BASELINE.new.json instead — promote it
    by hand when the budget change is intentional."""
    path = BASELINE_PATH
    if os.path.exists(BASELINE_PATH):
        with open(BASELINE_PATH) as f:
            prev = json.load(f)
        if (prev.get("quick"), prev.get("fused")) != (
            bool(args.quick), bool(args.fused)
        ):
            path = BASELINE_PATH.replace(".json", ".new.json")
            print(f"# budget mismatch with recorded baseline "
                  f"(quick={prev.get('quick')}, fused={prev.get('fused')}): "
                  f"writing {os.path.relpath(path)} instead")
    record = {
        "schema": 2,
        "created_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_sha": git_sha(),
        "backend": __import__("jax").default_backend(),
        "jax": __import__("jax").__version__,
        "quick": bool(args.quick),
        "smoke": bool(args.smoke),
        "fused": bool(args.fused),
        "suites": {
            name: [parse_row(r) for r in rows]
            for name, rows in suite_rows.items()
        },
        "quality": (
            [] if args.skip_quality else quality_rows(bool(args.quick))
        ),
        "profile": (
            [] if getattr(args, "skip_profile", False)
            else profile_rows(bool(args.quick))
        ),
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    print(f"# wrote {os.path.relpath(path)} "
          f"({sum(len(v) for v in record['suites'].values())} rows, "
          f"{len(record['quality'])} quality rows, "
          f"{len(record['profile'])} profile rows)")
    # every baseline write also appends to the trajectory history: the
    # baseline file is a snapshot (each PR overwrites it), the trajectory
    # is the record of how the numbers moved PR over PR
    os.makedirs(TRAJECTORY_DIR, exist_ok=True)
    stamp = record["created_utc"].replace(":", "").replace("-", "")
    traj = os.path.join(
        TRAJECTORY_DIR, f"{stamp}__{record['git_sha'][:12]}.json"
    )
    with open(traj, "w") as f:
        json.dump(record, f, indent=1)
    print(f"# appended {os.path.relpath(traj)}")


def roofline_summary():
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results",
                     "dryrun")
    if not os.path.isdir(d):
        print("# no dryrun results yet")
        return
    for f in sorted(os.listdir(d)):
        if not f.endswith(".json"):
            continue
        r = json.load(open(os.path.join(d, f)))
        if r.get("status") != "ok":
            print(f"roofline_{r['arch']}_{r['cell']}_{r['mesh']},0.00,"
                  f"status=skipped")
            continue
        rf = r["roofline"]
        dom = max(("t_compute_s", "t_memory_s", "t_collective_s"),
                  key=lambda k: rf[k])
        print(f"roofline_{r['arch']}_{r['cell']}_{r['mesh']},"
              f"{rf[dom]*1e6:.0f},"
              f"bottleneck={rf['bottleneck']};"
              f"tc={rf['t_compute_s']:.3f};tm={rf['t_memory_s']:.3f};"
              f"tcoll={rf['t_collective_s']:.3f};"
              f"useful={rf['useful_flops_ratio']:.3f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI sanity pass: quick budgets, smoke suites only")
    ap.add_argument("--only", default="")
    ap.add_argument("--fused", action="store_true",
                    help="route the bayesnet/compile suites through the "
                         "fused Pallas round kernels as well")
    ap.add_argument("--roofline", action="store_true")
    ap.add_argument("--skip-quality", action="store_true",
                    help="omit the sampling-quality sweep from the "
                         "baseline snapshot")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="runtime suite: also write a traced bursty-pass "
                         "snapshot (Perfetto JSON + .jsonl + .attrib.json) "
                         "alongside the baseline")
    ap.add_argument("--profile-out", default=None, metavar="PATH",
                    help="runtime suite (with --trace-out): also write the "
                         "compiled-artifact profile of the snapshot pass "
                         "(profile.json + .series.jsonl)")
    ap.add_argument("--skip-profile", action="store_true",
                    help="omit the static-cost profile sweep from the "
                         "baseline snapshot")
    args = ap.parse_args()
    if args.smoke:
        args.quick = True
    print("name,us_per_call,derived")
    if args.only:
        suites = {args.only: SUITES[args.only]}
    elif args.smoke:
        suites = {k: SUITES[k] for k in SMOKE_SUITES}
    else:
        suites = SUITES
    suite_rows = {}
    for name, fn in suites.items():
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        kwargs = {"quick": args.quick}
        if args.fused and name in FUSED_SUITES:
            kwargs["fused"] = True
        if args.trace_out and name == "runtime":
            kwargs["trace_out"] = args.trace_out
            if args.profile_out:
                kwargs["profile_out"] = args.profile_out
        suite_rows[name] = fn(**kwargs) or []
        print(f"# {name} took {time.time()-t0:.1f}s", flush=True)
    if set(suite_rows) == set(SUITES):
        write_baseline(suite_rows, args)
    else:
        # partial passes (--smoke / --only) must never clobber the
        # committed full-suite perf baseline
        print(f"# partial pass ({', '.join(suite_rows)}): "
              f"{os.path.relpath(BASELINE_PATH)} left untouched")
    if args.roofline:
        print("# --- roofline (from dry-run) ---")
        roofline_summary()


if __name__ == "__main__":
    from repro.core import compat

    compat.setup_compile_cache()  # the CLI only, never on import
    main()
