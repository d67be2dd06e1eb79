import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (harness deliverable e).

For every (architecture x input shape) cell, lower + compile the appropriate
step (train_step / prefill_step / serve_step) on the production mesh —
(16,16)=("data","model") single-pod and (2,16,16)=("pod","data","model")
multi-pod — and record:

  * compiled.memory_analysis()   (fits-per-device proof)
  * compiled.cost_analysis()     (FLOPs / bytes for the roofline)
  * collective bytes parsed from the optimized HLO (roofline 3rd term)

Results land in benchmarks/results/dryrun/<arch>__<cell>__<mesh>.json;
the driver mode (--all) runs each cell in a fresh subprocess so one cell's
failure or memory blow-up cannot poison the sweep, and completed cells are
skipped on re-run (resumable).

NOTE the XLA_FLAGS line above MUST precede any jax import — jax locks the
device count on first init.  Only this module sets it; tests and benchmarks
see the single real CPU device.
"""

import argparse
import json
import subprocess
import sys
import time
import traceback

RESULTS_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))),
    "benchmarks", "results", "dryrun",
)


def run_cell(arch: str, cell: str, mesh_kind: str, out_dir: str,
             opt_tag: str = "baseline") -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch import roofline as rl
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    spec = steps_lib.SHAPE_CELLS[cell]
    ok, why = steps_lib.cell_applicable(cfg, cell)
    if not ok:
        rec = {"arch": arch, "cell": cell, "mesh": mesh_kind,
               "opt": opt_tag, "status": "skipped", "reason": why}
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(
                out_dir, f"{arch}__{cell}__{mesh_kind}__{opt_tag}.json"),
                "w") as f:
            json.dump(rec, f, indent=1)
        print(f"[dryrun] {arch} {cell} {mesh_kind}: SKIPPED ({why[:60]})")
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    seq, batch = spec["seq"], spec["batch"]
    t0 = time.time()

    with mesh:
        if spec["kind"] == "train":
            with_batch, _ = steps_lib.make_train_step(cfg, mesh)
            batch_abs = steps_lib.abstract_batch(cfg, seq, batch)
            fn, _ = with_batch(batch_abs)
            args = (
                steps_lib.abstract_params(cfg),
                steps_lib.abstract_opt_state(
                    cfg, steps_lib.default_opt_cfg(cfg)),
                batch_abs,
            )
        elif spec["kind"] == "prefill":
            with_batch = steps_lib.make_prefill_step(cfg, mesh)
            batch_abs = steps_lib.abstract_batch(cfg, seq, batch)
            del batch_abs["labels"]
            fn = with_batch(batch_abs)
            args = (steps_lib.abstract_params(cfg), batch_abs)
        else:  # decode
            with_caches = steps_lib.make_serve_step(cfg, mesh, sampler="ky")
            caches_abs = steps_lib.abstract_caches(cfg, batch, seq)
            fn, _ = with_caches(caches_abs, batch)
            args = (
                steps_lib.abstract_params(cfg),
                jax.ShapeDtypeStruct((batch, 1), jnp.int32),
                caches_abs,
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((2,), jnp.uint32),
            )
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    mem_rec = {
        k: int(getattr(mem, k))
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes")
        if hasattr(mem, k)
    }
    cost = compiled.cost_analysis() or {}
    cost_rec = {
        k: float(cost[k]) for k in ("flops", "bytes accessed",
                                    "transcendentals") if k in cost
    }

    # trip-count-aware walk of the optimized (post-SPMD, per-device) HLO —
    # XLA's cost_analysis counts while bodies once (see hlo_cost docstring)
    from repro.launch import hlo_cost

    hlo = compiled.as_text()
    hc = hlo_cost.analyze(hlo)
    roof = rl.Roofline(
        flops=hc.flops,
        hbm_bytes=hc.hbm_bytes,
        collective_bytes=hc.collective_bytes,
        n_chips=1,  # the walked program is the per-device SPMD program
        device_kind=rl.TARGET_KIND,  # a CPU-side sweep: priced for v5e
        model_flops=rl.model_flops(cfg, spec["kind"], seq, batch) / n_chips,
    )
    coll = rl.CollectiveStats(
        {k: int(v) for k, v in hc.collective_by_op.items()},
        {k: int(v) for k, v in hc.collective_counts.items()},
    )
    rec = {
        "arch": arch,
        "cell": cell,
        "mesh": mesh_kind,
        "opt": opt_tag,
        "status": "ok",
        "n_chips": int(n_chips),
        "seq": seq,
        "batch": batch,
        "kind": spec["kind"],
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": mem_rec,
        "cost": cost_rec,
        "collectives": {
            "bytes_by_op": coll.bytes_by_op,
            "count_by_op": coll.count_by_op,
            "total_bytes": coll.total_bytes,
        },
        "roofline": roof.as_dict(),
    }
    os.makedirs(out_dir, exist_ok=True)
    fname = os.path.join(out_dir, f"{arch}__{cell}__{mesh_kind}__{opt_tag}.json")
    with open(fname, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[dryrun] {arch} {cell} {mesh_kind}: OK "
          f"(compile {t_compile:.0f}s, temp "
          f"{mem_rec.get('temp_size_in_bytes', 0)/2**30:.2f} GiB, "
          f"bottleneck {roof.bottleneck})")
    return rec


def drive_all(meshes, archs, cells, out_dir, tag="baseline"):
    """Run every pending cell in a fresh subprocess (resumable, isolated)."""
    from repro.configs import list_archs
    from repro.launch.steps import SHAPE_CELLS

    archs = archs or list_archs()
    cells = cells or list(SHAPE_CELLS)
    todo = []
    for mesh_kind in meshes:
        for arch in archs:
            for cell in cells:
                f = os.path.join(out_dir,
                                 f"{arch}__{cell}__{mesh_kind}__{tag}.json")
                if os.path.exists(f):
                    continue
                todo.append((arch, cell, mesh_kind))
    print(f"[dryrun] {len(todo)} cells to run")
    failures = []
    for i, (arch, cell, mesh_kind) in enumerate(todo):
        print(f"[dryrun] ({i+1}/{len(todo)}) {arch} {cell} {mesh_kind}",
              flush=True)
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
             "--cell", cell, "--mesh", mesh_kind, "--out", out_dir,
             "--tag", tag],
            capture_output=True, text=True, timeout=7200,
        )
        if r.returncode != 0:
            failures.append((arch, cell, mesh_kind))
            err_file = os.path.join(
                out_dir, f"{arch}__{cell}__{mesh_kind}__{tag}.err")
            with open(err_file, "w") as f:
                f.write(r.stdout[-5000:] + "\n---\n" + r.stderr[-10000:])
            print(f"[dryrun]   FAILED (log: {err_file})", flush=True)
        else:
            print(r.stdout.strip().splitlines()[-1] if r.stdout.strip()
                  else "[dryrun]   ok", flush=True)
    print(f"[dryrun] done: {len(todo) - len(failures)} ok, "
          f"{len(failures)} failed")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--cell", default=None,
                    choices=[None, "train_4k", "prefill_32k", "decode_32k",
                             "long_500k"])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true",
                    help="driver mode: subprocess per pending cell")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--tag", default="baseline")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        drive_all(meshes, [args.arch] if args.arch else None,
                  [args.cell] if args.cell else None, args.out, args.tag)
        return
    assert args.arch and args.cell
    try:
        run_cell(args.arch, args.cell, meshes[0], args.out, args.tag)
    except Exception:
        traceback.print_exc()
        sys.exit(1)


if __name__ == "__main__":
    main()
