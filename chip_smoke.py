"""Bring-up check of the posterior-query serving path on a TPU chip.

    python chip_smoke.py               # one chip: phases a-d below
    python chip_smoke.py --four-chips  # a 2x2 host: sharded MRF serving only

One process, no subprocesses.  One chip, by default:

  a. the device is a TPU and the Pallas kernels compile (not interpreted);
  b. the quickstart check at full size: `alarm` with evidence, 64 chains,
     500 sweeps, posterior within 0.05 TVD of variable elimination;
  c. a seeded query trace through `runtime.Engine` over real-size models —
     the full zipf zoo, `hepar2` and `pigs` (32 chains, long queries served
     in slices), and the `penguin` 64x64x4 / `art` 48x48x8 denoising MRFs
     — once fused and once unfused; every query's results must be
     bit-identical between the two;
  d. at least one BN and one MRF bucket executable holds Mosaic-compiled
     Pallas kernels (`tpu_custom_call`).

With `--four-chips` it runs only the sharded route: `penguin` queries
through an Engine whose slice spans a (1, 4) mesh (`mrf_fused_sharded`),
the same queries fused on one device, and a bit-identity check, plus a
check that the sharded state really lives on four devices.

Lines before the last are informational (wall and compile seconds per
phase are set-up figures, not metrics).  Any failure raises: the exit code
is nonzero and the result line is not printed.  The last line is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.core import compat  # noqa: E402  (fails outside a checkout)

SEED = 0
# the denoising grids of benchmarks/bench_mrf.py
MRF_IMAGES = {"penguin": (64, 64, 4), "art": (48, 48, 8)}
SLICE_ITERS = 50


class Phases:
    """Wall and compile seconds per phase; compile time is the sum of JAX's
    own backend-compile events (XLA and Mosaic), the set-up a cold process
    pays before serving."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax

        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.compile_s += duration

    def run(self, name: str, fn):
        c0, t0 = self.compile_s, time.perf_counter()
        print(f"[{name}] start", flush=True)
        out = fn()
        print(f"[{name}] ok: wall {time.perf_counter() - t0:.2f} s, "
              f"of it compiling {self.compile_s - c0:.2f} s", flush=True)
        return out


def phase_device():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs a TPU; JAX found {dev.platform!r}"
        )
    if compat.pallas_interpret():
        raise RuntimeError("Pallas kernels would run interpreted on a TPU")
    print(f"  device {dev.device_kind!r} x {len(jax.devices())}, "
          "Pallas kernels compiled by Mosaic", flush=True)


def phase_quickstart():
    import jax
    import numpy as np

    from repro.compile import compile_graph
    from repro.core.exact import ve_marginal
    from repro.core.graphs import bn_repository_replica

    bn = bn_repository_replica("alarm")
    evidence, query = {0: 1, 5: 0}, 20
    exact = ve_marginal(bn, query, evidence)
    prog = compile_graph(bn, evidence=evidence)
    marginals, _ = prog.run(
        jax.random.key(SEED), n_chains=64, n_iters=500, burn_in=125,
        sampler="lut_ky", backend="schedule",
    )
    approx = np.asarray(marginals)[query][: len(exact)]
    tvd = 0.5 * float(np.abs(exact - approx).sum())
    print(f"  alarm P(X{query} | e): exact {np.round(exact, 4)} "
          f"gibbs {np.round(approx, 4)} tvd {tvd:.4f}", flush=True)
    if not tvd < 0.05:
        raise AssertionError(f"quickstart TVD {tvd} >= 0.05")


def smoke_models() -> dict:
    from repro.core.graphs import GridMRF, bn_repository_replica
    from repro.runtime import trace as trace_mod

    models = trace_mod.zipf_models(quick=False)
    for name in ("hepar2", "pigs"):
        models[name] = bn_repository_replica(name)
    for name, (h, w, v) in MRF_IMAGES.items():
        models[name] = GridMRF(h, w, v, theta=1.2, h=2.0, name=name)
    return models


def smoke_queries(models: dict) -> list:
    """Two queries per model (three for the exact_ky models), seeded.  BN
    queries observe a fixed quarter of the nodes; `hepar2`/`pigs` are the
    long 32-chain queries the engine serves in slices; the small `grid`
    pins a few pixels on one query."""
    import numpy as np

    from repro.core.graphs import GridMRF
    from repro.core.mrf import make_denoising_problem
    from repro.runtime.batcher import Query

    rng = np.random.default_rng(SEED)
    long_bns = ("hepar2", "pigs")
    exact_ky = ("alarm", "hepar2")
    out = []

    def add(**kw):
        out.append(Query(qid=len(out), arrival_s=1e-4 * len(out),
                         seed=int(rng.integers(1 << 30)), **kw))

    for name, m in models.items():
        if isinstance(m, GridMRF):
            for i in range(2):
                _, noisy = make_denoising_problem(
                    m.height, m.width, m.n_labels, noise=0.25,
                    seed=int(rng.integers(1 << 16)),
                )
                pins = None
                if name == "grid" and i == 0:
                    sites = rng.choice(m.height * m.width, 3, replace=False)
                    pins = {int(p): int(rng.integers(m.n_labels))
                            for p in sites}
                add(model=name, image=noisy, evidence=pins, n_chains=8,
                    n_iters=40, burn_in=0)
            continue
        observed = rng.choice(m.n_nodes, m.n_nodes // 4, replace=False)
        long = name in long_bns
        samplers = ["lut_ky", "lut_ky"] + (["exact_ky"] if name in exact_ky
                                           else [])
        for sampler in samplers:
            ev = {int(v): int(rng.integers(m.cards[v])) for v in observed}
            add(model=name, evidence=ev, sampler=sampler,
                n_chains=32 if long else 8, n_iters=200 if long else 40,
                burn_in=50 if long else 10)
    return out


def serve(models, queries, **config):
    from repro.runtime.engine import Engine

    eng = Engine(models, **config)
    eng.submit(queries)
    results = eng.run()
    if sorted(results) != [q.qid for q in queries]:
        raise AssertionError("the engine did not serve every query")
    return eng, results


def same_results(a: dict, b: dict) -> list:
    """qids whose marginals or final chain states differ in any bit."""
    import numpy as np

    bad = []
    for qid in sorted(a):
        ra, rb = a[qid], b[qid]
        same = np.array_equal(ra.final_state, rb.final_state)
        if ra.marginals is not None:
            same = same and np.array_equal(ra.marginals, rb.marginals)
        if not same:
            bad.append(qid)
    return bad


def report_fused_buckets(eng, queries) -> None:
    """Which buckets the fused engine served fused, and which the static
    VMEM estimate (`analysis.kernel_lint`) demoted to the unfused route."""
    from repro.analysis import kernel_lint
    from repro.runtime import batcher as batcher_mod

    seen = set()
    for q in queries:
        graph = eng.graphs[q.model]
        key = batcher_mod.bucket_key(q, graph, eng.config.backend,
                                     eng.config.slice_iters, fused=True)
        tag = (q.model, q.sampler, q.n_chains)
        if tag in seen:
            continue
        seen.add(tag)
        if key.fused:
            verdict = "fused"
        elif batcher_mod.fused_eligible(graph.kind, q.sampler,
                                        eng.config.backend):
            fp = kernel_lint.estimate_footprint(graph, q.n_chains,
                                                q.sampler)
            verdict = (f"unfused: kernel_lint demoted it (VMEM estimate "
                       f"{fp.total_bytes / 2**20:.1f} MiB)")
        else:
            verdict = "unfused: no fused kernel for this sampler"
        print(f"  bucket {q.model:<9} {graph.kind} {q.sampler:<8} "
              f"{q.n_chains:>2} chains: {verdict}", flush=True)


def phase_trace():
    models = smoke_models()
    queries = smoke_queries(models)
    print(f"  {len(queries)} queries over {len(models)} models", flush=True)
    config = dict(slice_iters=SLICE_ITERS, max_batch=8)
    t0 = time.perf_counter()
    eng_f, fused = serve(models, queries, fused=True, **config)
    t1 = time.perf_counter()
    _, unfused = serve(models, queries, fused=False, **config)
    t2 = time.perf_counter()
    print(f"  fused pass {t1 - t0:.2f} s, unfused pass {t2 - t1:.2f} s "
          "(walls, compiles included)", flush=True)
    report_fused_buckets(eng_f, queries)
    bad = same_results(fused, unfused)
    if bad:
        raise AssertionError(f"fused and unfused results differ: qids {bad}")
    print(f"  {len(queries)} queries bit-identical fused vs unfused",
          flush=True)


def phase_kernels():
    """Profile one fused BN and one fused MRF bucket: their executables
    must hold Mosaic-compiled Pallas kernels."""
    from repro.obs import profile as profile_mod

    names = ("alarm", "penguin")
    models = smoke_models()
    queries = smoke_queries(models)
    pick = [next(q for q in queries if q.model == m and q.sampler == "lut_ky")
            for m in names]
    reg = profile_mod.enable()
    try:
        serve({m: models[m] for m in names}, pick, fused=True,
              slice_iters=SLICE_ITERS)
    finally:
        profile_mod.disable()
    found = {"bn": 0, "mrf": 0}
    for prof in reg.profiles.values():
        meta = prof.meta
        print(f"  {meta.get('model')} {meta.get('kind')} "
              f"fused={meta.get('fused')}: {prof.mosaic_kernels} "
              "tpu_custom_call", flush=True)
        if meta.get("fused") and prof.mosaic_kernels > 0:
            found[meta.get("kind")] += 1
    if not (found["bn"] and found["mrf"]):
        raise AssertionError(f"fused executables without Mosaic kernels: "
                             f"{found}")


def phase_four_chips():
    """Sharded penguin serving on a (1, 4) mesh vs one device, fused."""
    import jax
    import numpy as np

    from repro.compile import canonicalize, compile_graph

    if len(jax.devices()) != 4:
        raise SystemExit(f"--four-chips needs 4 devices, JAX found "
                         f"{len(jax.devices())}")
    mrf = smoke_models()["penguin"]
    models = {"penguin": mrf}
    queries = smoke_queries(models)
    eng_s, sharded = serve(models, queries, fused=True, n_workers=4,
                           shard_width=4,
                           shard_min_sites=mrf.height * mrf.width)
    eng_1, single = serve(models, queries, fused=True)
    n_sharded = eng_s.metrics.summary()["sharded_batches"]
    if n_sharded == 0 or eng_1.metrics.summary()["sharded_batches"]:
        raise AssertionError("the sharded engine never took the sharded "
                             "route (or the one-device engine did)")
    bad = same_results(sharded, single)
    if bad:
        raise AssertionError(f"sharded and one-device results differ: "
                             f"qids {bad}")
    print(f"  {len(queries)} queries, {n_sharded} sharded dispatches, "
          "bit-identical to one device", flush=True)

    # where the sharded state lives: four devices, a quarter of the rows each
    mesh = compat.make_mesh((1, 4), ("data", "model"))
    if len({d.id for d in mesh.devices.flat}) != 4:
        raise AssertionError(f"mesh does not span 4 devices: {mesh}")
    prog = compile_graph(canonicalize(mrf, evidence_mode="runtime"),
                         pipeline="runtime")
    labels = prog.run_sharded(
        jax.random.key(SEED), mesh, n_chains=8, n_iters=4, fused=True,
        evidence=np.asarray(queries[0].image, np.int32),
    )
    shards = labels.addressable_shards
    devices = {s.device.id for s in shards}
    rows = {s.data.shape[1] for s in shards}
    print(f"  sharded labels {labels.shape} on devices {sorted(devices)}, "
          f"{sorted(rows)} rows per shard", flush=True)
    if len(devices) != 4 or rows != {labels.shape[1] // 4}:
        raise AssertionError("sharded chain state is not split over 4 devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded route on a 4-chip host")
    args = ap.parse_args(argv)

    import jax

    print(f"compile cache: {compat.setup_compile_cache()}", flush=True)
    phases = Phases()
    phases.run("a device", phase_device)
    if args.four_chips:
        phases.run("four-chip sharded MRF", phase_four_chips)
    else:
        phases.run("b quickstart", phase_quickstart)
        phases.run("c serving trace", phase_trace)
        phases.run("d kernels in executables", phase_kernels)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
