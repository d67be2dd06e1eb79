"""Compiled-artifact roofline profiler for bucket/program executables.

The runtime traces *when* dispatches run (``obs.tracer`` spans) and the
diag layer checks *whether* samples are correct; this module answers
*what the compiled code actually does*.  At first jit of a bucket
executable (hooked in ``runtime/batcher.py``) or a schedule program
(hooked in ``compile/program.py``), the profiler:

  1. lowers + AOT-compiles the exact call about to execute,
  2. runs the trip-count-aware ``launch/hlo_cost.analyze()`` over the
     optimized HLO and ``compiled.cost_analysis()`` for XLA's own view,
  3. classifies the roofline bottleneck (compute / memory / collective)
     from ``launch/roofline.py`` terms,
  4. caches the result by executable signature and emits an
     ``hlo_cost`` instant into the trace.

``join_dispatches`` then joins the cached static costs against measured
``dispatch`` span walls (via the ``profile_sig`` arg the executor stamps
on every span) to report achieved-vs-peak per bucket and per comm
mechanism.  ``static_profile_sweep`` compiles a fixed model zoo at a
tiny budget — the rows ``benchmarks/run.py`` records in the baseline and
``benchmarks/check_regression.py`` diffs as the static-cost drift gate.

Module state mirrors ``obs.tracer``: profiling is off by default
(``enable()`` / ``disable()``, or the ``REPRO_PROFILE`` env var), and
the batcher/program hooks are no-ops while disabled.  Signature strings
and static costs contain no wall-clock terms; only the per-capture
``capture_s`` diagnostic does, and it is excluded from deterministic
exports.
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import time

from repro.launch import hlo_cost as hlo_cost_mod
from repro.launch import roofline as roofline_mod
from repro.obs import tracer

# optimized-HLO collective op -> the schedule comm mechanism it lowers
# from (the reverse of compile/backend.py MECHANISM_COLLECTIVES)
HLO_OP_MECHANISM = {
    "all-reduce": "psum_broadcast",
    "collective-permute": "ppermute_halo",
    "all-gather": "all_gather",
    "all-to-all": "all_to_all",
    "reduce-scatter": "reduce_scatter",
}

BOTTLENECKS = ("compute", "memory", "collective")


def bucket_signature(key, n_padded: int, route: str = "vmap",
                     shard_width: int = 1) -> str:
    """Deterministic signature of a batcher bucket executable.

    One signature per distinct jit specialization: every field that is a
    static argument (or shapes one, like the pad width and clamp set)
    participates.  A sharded-route dispatch executes a different
    specialization (the shard_map body over a mesh slice), so the route
    and slice width extend the signature there; the vmap format is
    unchanged.  Pure string math — safe to stamp on every dispatch span
    whether or not profiling is enabled.
    """
    clamp = ",".join(str(n) for n in key.clamp_nodes)
    parts = [
        "bucket", key.program_key[:16], key.kind, key.backend, key.sampler,
        f"pad{n_padded}", f"ch{key.n_chains}", f"it{key.n_iters}",
        f"bi{key.burn_in}", f"th{key.thin}", f"cl[{clamp}]",
        f"pins{int(key.has_pins)}", f"fused{int(key.fused)}",
        f"res{int(key.resumed)}", f"diag{int(key.diagnostics)}",
    ]
    if route != "vmap":
        parts += [route, f"sh{shard_width}"]
    return "|".join(parts)


def program_signature(program, *, n_chains, n_iters, burn_in, thin,
                      sampler, fused) -> str:
    """Signature of a whole-program (unbatched ``run()``) executable."""
    return "|".join([
        "run", program.program_key[:16], program.kind, sampler,
        f"ch{n_chains}", f"it{n_iters}", f"bi{burn_in}", f"th{thin}",
        f"fused{int(fused)}",
    ])


@dataclasses.dataclass
class BucketProfile:
    """Static cost + roofline classification of one compiled executable."""

    sig: str
    meta: dict
    flops: float
    hbm_bytes: float
    collective_bytes: float
    collective_by_op: dict
    xla_flops: float
    xla_bytes: float
    t_compute_s: float
    t_memory_s: float
    t_collective_s: float
    bottleneck: str
    capture_s: float  # wall time of the AOT compile+analysis (diagnostic)
    # Pallas kernels Mosaic compiled into the executable (0 when the
    # kernels ran interpreted, or the bucket is unfused)
    mosaic_kernels: int = 0

    @property
    def roofline_s(self) -> float:
        return max(self.t_compute_s, self.t_memory_s, self.t_collective_s)

    def as_dict(self, deterministic: bool = True) -> dict:
        d = {
            "sig": self.sig,
            "meta": dict(self.meta),
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "collective_by_op": dict(self.collective_by_op),
            "xla_flops": self.xla_flops,
            "xla_bytes": self.xla_bytes,
            "t_compute_s": self.t_compute_s,
            "t_memory_s": self.t_memory_s,
            "t_collective_s": self.t_collective_s,
            "roofline_s": self.roofline_s,
            "bottleneck": self.bottleneck,
            "mosaic_kernels": self.mosaic_kernels,
        }
        if not deterministic:
            d["capture_s"] = round(self.capture_s, 6)
        return d


# how a Mosaic-compiled Pallas kernel appears in an executable's HLO
MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'


def target_kind() -> str:
    """The chip kind the roofline prices executables against: the chip this
    process runs on, or — on CPU, where nothing runs on a chip — the
    `roofline.TARGET_KIND` the kernels are written for."""
    import jax

    dev = jax.devices()[0]
    return dev.device_kind if dev.platform == "tpu" else \
        roofline_mod.TARGET_KIND


class ProfileRegistry:
    """Cache of :class:`BucketProfile` keyed by executable signature."""

    def __init__(self, device_kind: str | None = None):
        self.device_kind = device_kind or target_kind()
        roofline_mod.peaks(self.device_kind)  # an unknown kind fails here
        self.profiles: dict = {}
        self.hits = 0
        self.errors: dict = {}

    def capture(self, sig: str, lower, *, n_chips: int = 1,
                **meta) -> BucketProfile:
        """Profile the executable ``lower()`` lowers, once per signature.

        ``lower`` is a zero-arg thunk returning a jax ``Lowered`` (so
        cache hits never trace).  The AOT ``.compile()`` here is
        separate from the jit's own executable cache — one extra XLA
        compile per signature is the cost of profiling.
        """
        prof = self.profiles.get(sig)
        if prof is not None:
            self.hits += 1
            return prof
        t0 = time.perf_counter()
        compiled = lower().compile()
        text = compiled.as_text()
        cost = hlo_cost_mod.analyze(text)
        xla_flops = xla_bytes = 0.0
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):  # older jax: per-device list
                ca = ca[0] if ca else {}
            xla_flops = float(ca.get("flops", 0.0) or 0.0)
            xla_bytes = float(ca.get("bytes accessed", 0.0) or 0.0)
        except Exception as e:  # backend without cost_analysis support
            self.errors[sig] = f"cost_analysis: {e}"
        roof = roofline_mod.Roofline(
            flops=cost.flops, hbm_bytes=cost.hbm_bytes,
            collective_bytes=cost.collective_bytes, n_chips=n_chips,
            device_kind=self.device_kind,
        )
        prof = BucketProfile(
            sig=sig, meta=dict(meta),
            flops=cost.flops, hbm_bytes=cost.hbm_bytes,
            collective_bytes=cost.collective_bytes,
            collective_by_op={
                k: v for k, v in sorted(cost.collective_by_op.items()) if v
            },
            xla_flops=xla_flops, xla_bytes=xla_bytes,
            t_compute_s=roof.t_compute, t_memory_s=roof.t_memory,
            t_collective_s=roof.t_collective, bottleneck=roof.bottleneck,
            capture_s=time.perf_counter() - t0,
            mosaic_kernels=text.count(MOSAIC_CALL),
        )
        self.profiles[sig] = prof
        if tracer.enabled():
            tracer.instant(
                "hlo_cost", cat="cost", sig=sig,
                flops=prof.flops, hbm_bytes=prof.hbm_bytes,
                collective_bytes=prof.collective_bytes,
                bottleneck=prof.bottleneck,
                **{k: meta[k] for k in ("model", "kind", "program")
                   if meta.get(k) is not None},
            )
        return prof

    def rows(self, deterministic: bool = True) -> list:
        return [self.profiles[s].as_dict(deterministic)
                for s in sorted(self.profiles)]


# -- module state (mirrors obs.tracer) --------------------------------------

_REGISTRY = None


def enabled() -> bool:
    return _REGISTRY is not None


def get() -> "ProfileRegistry | None":
    return _REGISTRY


def enable() -> ProfileRegistry:
    global _REGISTRY
    _REGISTRY = ProfileRegistry()
    return _REGISTRY


def disable() -> None:
    global _REGISTRY
    _REGISTRY = None


# -- capture hooks ----------------------------------------------------------

def capture_bucket(program, key, n_padded, jitted, args, kwargs, *,
                   model=None) -> "BucketProfile | None":
    """Batcher hook: profile the bucket call about to execute.

    Called with the exact ``(args, kwargs)`` of the jitted bucket entry;
    ``jitted.lower(*args, **kwargs)`` only traces (donation happens at
    execution), so the subsequent real call is untouched.
    """
    reg = get()
    if reg is None:
        return None
    sig = bucket_signature(key, n_padded)
    return reg.capture(
        sig, lambda: jitted.lower(*args, **kwargs),
        model=model, kind=key.kind, program=key.program_key,
        sampler=key.sampler, backend=key.backend, fused=key.fused,
        resumed=key.resumed, n_padded=n_padded,
        n_chains=key.n_chains, n_iters=key.n_iters, route="vmap",
    )


def capture_program(program, *, n_chains, n_iters, burn_in=50, thin=1,
                    sampler="lut_ky", fused=False,
                    registry=None) -> "BucketProfile | None":
    """Profile a whole-program schedule executable (``program.run()``).

    Lowers the same ``compile/backend.py`` jitted entry the run would
    execute, with placeholder evidence/carry (None — the no-clamp/no-pin
    specialization ``run()`` uses on the profiled branches).  The
    backend import is deferred so ``repro.obs`` never drags the compile
    chain in at import time.
    """
    import jax
    import jax.numpy as jnp

    from repro.compile import backend as backend_mod
    from repro.core import compat

    reg = registry if registry is not None else get()
    if reg is None:
        return None
    sig = program_signature(
        program, n_chains=n_chains, n_iters=n_iters, burn_in=burn_in,
        thin=thin, sampler=sampler, fused=fused,
    )
    if sig in reg.profiles:
        reg.hits += 1
        return reg.profiles[sig]
    ex = program.schedule_executable()
    interpret = compat.pallas_interpret()
    if program.kind == "bn":
        def lower():
            return backend_mod._run_bn_rounds.lower(
                ex.cbn, ex.round_groups, jax.random.key(0), None, None,
                None, n_chains=n_chains, n_iters=n_iters, burn_in=burn_in,
                sampler=sampler, thin=thin, return_state=False,
                fused=fused, interpret=interpret,
            )
    else:
        ev = jnp.zeros((ex.mrf.height, ex.mrf.width), jnp.int32)

        def lower():
            return backend_mod._run_mrf_rounds.lower(
                ex.mrf, ex.parities, ev, jax.random.key(0), None, None,
                None, n_chains=n_chains, n_iters=n_iters, sampler=sampler,
                fused=fused, interpret=interpret, return_state=False,
            )
    return reg.capture(
        sig, lower, model=program.ir.name, kind=program.kind,
        program=program.program_key, sampler=sampler, fused=fused,
        n_chains=n_chains, n_iters=n_iters, route="run",
    )


# -- joining static costs against measured dispatch walls -------------------

def join_dispatches(profiles, events, device_kind: str | None = None) -> dict:
    """Join cached static costs against measured ``dispatch`` spans.

    ``profiles`` maps sig -> :class:`BucketProfile` (or its dict form);
    ``events`` is a list of event dicts (``export.events_as_dicts`` with
    wall fields kept, or ``export.load_jsonl`` output).  Returns rows
    aggregated per signature with achieved-vs-peak ratios, per-mechanism
    comm rows, and the dispatches no profile covered.  Sharded-route
    dispatches attribute like any other: the executor stamps their
    route-qualified ``profile_sig`` and the sharded engines capture the
    shard_map executable under the same signature, so a sharded dispatch
    without a profile is an unattributed finding, not a skip.
    Comm rows compare against `device_kind`'s ICI peak (`target_kind()`
    when None).
    """
    ici_bw = roofline_mod.peaks(device_kind or target_kind()).ici_bw
    rows: dict = {}
    unattributed: dict = {}
    n_dispatches = 0
    n_sharded = 0
    for ev in events:
        if ev.get("name") != "dispatch":
            continue
        a = ev.get("args") or {}
        w = ev.get("wargs") or {}
        n_dispatches += 1
        if a.get("route") != "vmap":
            n_sharded += 1
        sig = a.get("profile_sig")
        prof = profiles.get(sig)
        if prof is None:
            u = unattributed.setdefault(sig or "<unsigned>", {
                "sig": sig, "model": a.get("model"),
                "program": a.get("program"), "n_dispatches": 0,
            })
            u["n_dispatches"] += 1
            continue
        pd = prof.as_dict() if isinstance(prof, BucketProfile) else dict(prof)
        row = rows.get(sig)
        if row is None:
            row = rows[sig] = {
                **pd, "n_dispatches": 0, "n_measured": 0,
                "measured_total_s": 0.0, "service_total_s": 0.0,
            }
        row["n_dispatches"] += 1
        row["service_total_s"] += float(a.get("service_s") or 0.0)
        ms = w.get("measured_s")
        if ms is not None:
            row["n_measured"] += 1
            row["measured_total_s"] += float(ms)
    out_rows = []
    comm: dict = {}
    for sig in sorted(rows):
        row = rows[sig]
        meas = (row["measured_total_s"] / row["n_measured"]
                if row["n_measured"] else None)
        row["measured_mean_s"] = meas
        row["service_total_s"] = round(row["service_total_s"], 9)
        row["measured_total_s"] = round(row["measured_total_s"], 9)
        if meas and meas > 0:
            row["achieved_flops"] = row["flops"] / meas
            row["achieved_hbm_bw"] = row["hbm_bytes"] / meas
            row["peak_frac"] = min(1.0, row["roofline_s"] / meas)
        else:
            row["achieved_flops"] = row["achieved_hbm_bw"] = None
            row["peak_frac"] = None
        for op, nbytes in row.get("collective_by_op", {}).items():
            mech = HLO_OP_MECHANISM.get(op, op)
            c = comm.setdefault(mech, {
                "mechanism": mech, "hlo_op": op, "bytes_per_dispatch": 0.0,
                "total_bytes": 0.0, "measured_total_s": 0.0,
                "n_dispatches": 0,
            })
            c["bytes_per_dispatch"] += nbytes
            c["total_bytes"] += nbytes * row["n_dispatches"]
            c["measured_total_s"] += row["measured_total_s"]
            c["n_dispatches"] += row["n_dispatches"]
        out_rows.append(row)
    comm_rows = []
    for mech in sorted(comm):
        c = comm[mech]
        c["measured_total_s"] = round(c["measured_total_s"], 9)
        c["achieved_bw"] = (
            c["total_bytes"] / c["measured_total_s"]
            if c["measured_total_s"] > 0 else None
        )
        c["peak_frac"] = (
            min(1.0, c["achieved_bw"] / ici_bw)
            if c["achieved_bw"] else None
        )
        comm_rows.append(c)
    return {
        "rows": out_rows,
        "comm": comm_rows,
        "unattributed": [unattributed[k] for k in sorted(unattributed)],
        "n_dispatches": n_dispatches,
        "n_sharded": n_sharded,
    }


def write_profile(path, registry, events) -> dict:
    """Join + write the ``profile.json`` artifact; returns the record."""
    joined = join_dispatches(registry.profiles, events, registry.device_kind)
    pk = roofline_mod.peaks(registry.device_kind)
    rec = {
        "schema": 1,
        "peaks": {"device_kind": registry.device_kind, "flops": pk.flops,
                  "hbm_bw": pk.hbm_bw, "ici_bw": pk.ici_bw},
        "buckets": registry.rows(deterministic=False),
        "capture_hits": registry.hits,
        "capture_errors": dict(sorted(registry.errors.items())),
        "joined": joined,
    }
    pathlib.Path(path).write_text(json.dumps(rec, indent=1, sort_keys=True))
    return rec


def validate_profile(rec: dict) -> list:
    """Sanity problems in a saved ``profile.json`` ('' when healthy)."""
    problems = []
    if rec.get("schema") != 1:
        problems.append(f"unknown profile schema {rec.get('schema')!r}")
        return problems
    buckets = rec.get("buckets", [])
    if not buckets:
        problems.append("no captured bucket profiles")
    for b in buckets:
        if b.get("bottleneck") not in BOTTLENECKS:
            problems.append(
                f"{b.get('sig')}: bad bottleneck {b.get('bottleneck')!r}")
        if not b.get("hbm_bytes", 0) > 0:
            problems.append(f"{b.get('sig')}: hbm_bytes must be > 0")
    joined = rec.get("joined", {})
    for u in joined.get("unattributed", []):
        problems.append(
            f"unattributed dispatches: sig={u.get('sig')!r} "
            f"x{u.get('n_dispatches')}")
    return problems


# -- static sweep for the baseline / drift gate -----------------------------

# fixed tiny budget: the gate compares static HLO costs, not wall time,
# so the sweep only needs each executable's *shape*, cheaply
SWEEP_BUDGET = dict(n_chains=8, n_iters=32, burn_in=8, thin=1)
SWEEP_BN_MODELS = ("survey", "alarm")
SWEEP_GRID = 8


def static_profile_sweep(quick: bool = False) -> list:
    """Per-signature static costs over a fixed model zoo.

    Deterministic rows (signatures embed the content-hash program key)
    recorded by ``benchmarks/run.py`` into the baseline and re-derived
    by ``check_regression.py`` — flops/hbm_bytes/collective_bytes drift
    per signature fails CI without needing hardware.
    """
    from repro.compile import compile_graph
    from repro.core.graphs import GridMRF, bn_repository_replica

    # a static sweep: priced for the chip the kernels target, whatever runs it
    reg = ProfileRegistry(device_kind=roofline_mod.TARGET_KIND)
    models = SWEEP_BN_MODELS[:1] if quick else SWEEP_BN_MODELS
    progs = [compile_graph(bn_repository_replica(name)) for name in models]
    progs.append(compile_graph(GridMRF(
        SWEEP_GRID, SWEEP_GRID, 3, theta=1.1, h=1.8,
        name=f"grid{SWEEP_GRID}",
    )))
    for prog in progs:
        for fused in (False, True):
            capture_program(prog, sampler="lut_ky", fused=fused,
                            registry=reg, **SWEEP_BUDGET)
    return reg.rows(deterministic=True)


# honor the environment once at import, like tracer's REPRO_TRACE
if os.environ.get("REPRO_PROFILE", "") not in ("", "0"):
    enable()
