"""One run of one cell: set-up, the closed-loop window, the trace reduction
and the check against the reference.

The timed path is the program's serving entry, `Engine.submit` then
`Engine.run`, on one engine built from the configuration's engine options.
A round of the closed loop submits one query per client and runs the
engine until every one is answered (the engine drains what it was given,
synchronously); each query's latency is the round's wall time, from its
submission to its results on the host.  The window starts rounds until
`seconds` have passed and ends when the last one returns, so the rate
counts all queries over all the window's time.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import pathlib
import random
import shutil
import tempfile
import time
import types

import numpy as np

from bench import check as check_mod
from bench import devtrace
from bench import models as models_mod
from bench import spec as spec_mod
from bench import generator

# Rounds before the window: the first compiles and runs the program's
# first-use checks, the second shows every shape is warm and times a round.
WARMUP_ROUNDS = 2
# Rounds drawn for the window: this many times what the warm round's time
# predicts, so a faster window cannot run out of queries.
STREAM_MARGIN = 4


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform!r} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


class CompileLog:
    """JAX's own compile events, timestamped on the host clock."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    }

    COUNTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        import jax

        self.events: list[tuple[str, float, float]] = []  # (kind, t, secs)
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_count)

    def _on(self, event, duration, **_):
        kind = self.EVENTS.get(event)
        if kind is not None:
            self.events.append((kind, time.perf_counter(), float(duration)))

    def _on_count(self, event, **_):
        kind = self.COUNTS.get(event)
        if kind is not None:
            self.events.append((kind, time.perf_counter(), 1.0))

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)
        jax.monitoring.unregister_event_listener(self._on_count)

    def seconds(self, t0: float, t1: float) -> dict:
        """Seconds of each compile stage (nested traces count once each),
        and persistent-cache hits and misses, between t0 and t1."""
        out = dict.fromkeys([*self.EVENTS.values(), *self.COUNTS.values()],
                            0.0)
        for kind, t, s in self.events:
            if t0 <= t <= t1:
                out[kind] += s
        return out

    def compiles(self, t0: float, t1: float) -> int:
        return sum(1 for kind, t, _ in self.events
                   if kind == "backend_compile_s" and t0 <= t <= t1)


class GcLog:
    """Pauses of Python's garbage collector, timestamped on the host clock."""

    def __init__(self):
        self.pauses: list[tuple[int, float, float]] = []  # (gen, t, secs)
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            t = time.perf_counter()
            self.pauses.append((info["generation"], t, t - self._t0))
            self._t0 = None

    def close(self):
        gc.callbacks.remove(self._on)

    def between(self, t0: float, t1: float) -> list[tuple[int, float]]:
        return [(g, s) for g, t, s in self.pauses if t0 <= t <= t1]


def program_models(config: dict, plain: dict) -> dict:
    """The generated models as the program's own model types."""
    from repro.core.graphs import DiscreteBayesNet, GridMRF

    out = {}
    for name, m in plain.items():
        if config["kind"] == "bn":
            out[name] = DiscreteBayesNet(
                np.asarray(m["cards"]), [list(p) for p in m["parents"]],
                list(m["cpts"]), name=name)
        else:
            out[name] = GridMRF(m["height"], m["width"], m["labels"],
                                name=name, **models_mod.mrf_fields(m))
    return out


def program_query(q: generator.QuerySpec):
    from repro.runtime.batcher import Query

    return Query(qid=q.qid, model=q.model, evidence=q.evidence, image=q.image,
                 n_chains=q.n_chains, n_iters=q.n_iters, burn_in=q.burn_in,
                 thin=q.thin, sampler=q.sampler, seed=q.seed)


@dataclasses.dataclass
class Window:
    """What the closed loop saw."""

    latencies: list = dataclasses.field(default_factory=list)
    round_s: list = dataclasses.field(default_factory=list)
    rounds: int = 0
    submitted: int = 0
    lost: int = 0
    dispatches: int = 0
    n_real: int = 0
    n_padded: int = 0
    t0: float = 0.0
    t1: float = 0.0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def answered(self) -> int:
        return len(self.latencies)


def queries_per_s(w: Window) -> float:
    """All queries answered over all of the window's time."""
    return w.answered / w.seconds


def p95_ms(latencies) -> float:
    """The 95th percentile of every latency (linear interpolation)."""
    return float(np.percentile(np.asarray(latencies), 95)) * 1e3


class Reservoir:
    """A uniform sample of k of the window's answers, drawn from the seed
    (Vitter's algorithm R)."""

    def __init__(self, k: int, seed: int):
        self.k, self.n, self.items = k, 0, []
        self.rng = random.Random(seed)

    def offer(self, item):
        self.n += 1
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = self.rng.randrange(self.n)
            if j < self.k:
                self.items[j] = item


def closed_loop(engine, rounds, seconds: float, window: Window,
                reservoir: Reservoir | None = None, on_round=None) -> None:
    """Serve `rounds` ([(specs, program queries)]) one after another until
    `seconds` have passed; accumulate into `window`."""
    import jax

    window.t0 = time.perf_counter()
    for specs, queries in rounds:
        if time.perf_counter() - window.t0 >= seconds:
            break
        with jax.profiler.TraceAnnotation("round"):
            with jax.profiler.TraceAnnotation("submit"):
                t_submit = time.perf_counter()
                engine.submit(queries)
            with jax.profiler.TraceAnnotation("run"):
                results = engine.run()
            t_done = time.perf_counter()
            with jax.profiler.TraceAnnotation("collect"):
                records = engine.metrics.batch_records
                window.rounds += 1
                window.round_s.append(t_done - t_submit)
                window.submitted += len(queries)
                window.dispatches += len(records)
                window.n_real += sum(r.n_real for r in records)
                window.n_padded += sum(r.n_padded for r in records)
                for spec in specs:
                    r = results.get(spec.qid)
                    if r is None:
                        window.lost += 1
                        continue
                    window.latencies.append(t_done - t_submit)
                    if reservoir is not None:
                        reservoir.offer((spec, check_mod.Answer(
                            r.final_state, r.marginals)))
        if on_round is not None:
            on_round(window)
    else:
        if math.isfinite(seconds):
            raise RuntimeError("the query stream ran out inside the window")
    window.t1 = time.perf_counter()


def _program_spans(events) -> dict:
    """Set-up seconds the program's own tracer attributes to its passes and
    first-use cross-checks (outermost spans only)."""
    out = {"passes_s": 0.0, "cross_checks_s": 0.0}
    checks = []
    for e in events:
        if e.kind != "span" or e.wall_t0 is None:
            continue
        if e.name.startswith("pass:"):
            out["passes_s"] += e.wall_t1 - e.wall_t0
        elif e.name in ("cross_check", "cross_check_fused", "clamp_lowering"):
            checks.append((e.wall_t0, e.wall_t1))
    end = -math.inf
    for a, b in sorted(checks):
        if b > end:
            out["cross_checks_s"] += b - max(a, end)
            end = b
    return out


def run_cell(root: pathlib.Path, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_tpu: bool = True,
             trace_dir: str | None = None, log=print) -> dict:
    """One run; returns the result object the last line prints."""
    cell = spec_mod.load_cell(root, workload)
    device = device_info(cell.chips, require_tpu)
    from repro.obs import tracer as program_tracer

    t_imported = time.perf_counter()
    compiles = CompileLog()
    collections = GcLog()
    try:
        engine, plain, rounds, setup = _set_up(
            cell, seed, seconds, compiles, t_start, t_imported, program_tracer)
        log("setup " + " ".join(f"{k}={v}" for k, v in setup.items()))
        window, reservoir, summary, traced = _serve(
            engine, cell.traffic, seed, seconds, rounds, trace, trace_dir, log)
        device["memory_peak_bytes"] = memory_peak_bytes()
        n_compiles = compiles.compiles(window.t0, window.t1)
        in_window = compiles.seconds(window.t0, window.t1)
        pauses = collections.between(window.t0, window.t1)
    finally:
        compiles.close()
        collections.close()
        gc.unfreeze()
        program_tracer.disable()
    del engine, rounds  # the reference runs after the program's state is gone

    reference = check_mod.Reference(cell.config, cell.traffic, plain)
    mismatched = check_mod.mismatches(reference, reservoir.items)
    k = cell.traffic["check_queries"]
    checks = {
        "mismatched_queries": {"value": mismatched, "limit": 0},
        "lost_queries": {"value": window.lost, "limit": 0},
        "checked_queries": {"value": len(reservoir.items), "limit": k},
    }
    out = {"correct": mismatched == 0 and window.lost == 0
           and len(reservoir.items) == k,
           "attempted": window.submitted, "failed": window.lost}
    if not trace:
        values = {"queries_per_s": queries_per_s(window),
                  "query_p95_ms": p95_ms(window.latencies),
                  "setup_s": setup["setup_s"]}
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    else:
        per_round = generator.site_updates(cell.traffic, cell.config, plain)
        ctx = types.SimpleNamespace(
            kind=cell.config["kind"], trace=summary,
            traced_site_updates=traced.rounds * per_round,
            traced_dispatches=traced.dispatches,
            n_real=window.n_real, n_padded=window.n_padded,
            compiles_in_window=n_compiles)
        out["metrics"] = {}
        for m in cell.per_layer:
            value = spec_mod.metric_reader(m["name"])(ctx)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            out["breakdown"] = {"device_ops": summary.top_ops,
                                "idle_gaps": summary.gaps}
    out["device"] = device
    _log_window(log, window, n_compiles, in_window, pauses)
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    out["checks"] = checks
    return out


def _log_window(log, window, n_compiles, in_window, pauses):
    """What the window saw, for the record on standard error: compile work
    inside it, its round times, and the collector's pauses."""
    log(f"window rounds={window.rounds} answered={window.answered} "
        f"seconds={window.seconds} dispatches={window.dispatches} "
        f"compiles={n_compiles} "
        + " ".join(f"{k}={v}" for k, v in in_window.items()))
    rs = window.round_s
    if rs:
        slowest = sorted(range(len(rs)), key=lambda i: -rs[i])[:3]
        log(f"rounds min_s={min(rs)} median_s={float(np.median(rs))} "
            f"max_s={max(rs)} slowest={[(i, rs[i]) for i in slowest]}")
    log(f"gc collections={len(pauses)} "
        f"gen2={sum(1 for g, _ in pauses if g == 2)} "
        f"max_pause_s={max((p for _, p in pauses), default=0.0)}")


def _set_up(cell, seed, seconds, compiles, t_start, t_imported,
            program_tracer):
    """Build the engine, warm every bucket shape the window uses, and draw
    the window's query stream."""
    from repro.runtime.engine import Engine, EngineConfig

    config, traffic = cell.config, cell.traffic
    plain = models_mod.build(config)
    engine = Engine(program_models(config, plain),
                    EngineConfig(**config["engine"]))
    t_built = time.perf_counter()
    tracer = program_tracer.enable()
    warm = generator.Stream(traffic, config, plain, seed, generator.WARMUP)
    round_s = []
    for specs in warm.rounds(WARMUP_ROUNDS):
        w = Window()
        closed_loop(engine, [(specs, [program_query(q) for q in specs])],
                    math.inf, w)
        round_s.append(w.seconds)
    program_tracer.disable()
    n_rounds = int(STREAM_MARGIN * seconds / max(round_s[-1], 1e-3)) + 8
    stream = generator.Stream(traffic, config, plain, seed, generator.WINDOW)
    rounds = [(s, [program_query(q) for q in s])
              for s in stream.rounds(n_rounds)]
    # what set-up left on the heap (traced programs, the query stream) is
    # kept for the whole run: move it out of the collector's reach, so a
    # full collection inside the window does not walk it
    gc.collect()
    gc.freeze()
    t_setup = time.perf_counter()
    setup = {
        "setup_s": t_setup - t_start,
        "imports_s": t_imported - t_start,
        "build_s": t_built - t_imported,
        "warmup_rounds_s": round_s,
        "stream_s": t_setup - t_built - sum(round_s),
        **_program_spans(tracer.events),
        **compiles.seconds(t_imported, t_setup),
    }
    return engine, plain, rounds, setup


def _serve(engine, traffic, seed, seconds, rounds, trace, trace_dir, log):
    """The window; with `trace`, a profiler trace of its first
    `trace_seconds`, reduced to a `devtrace.Summary` (None if unreadable)."""
    import jax

    window = Window()
    reservoir = Reservoir(traffic["check_queries"], seed)
    traced = types.SimpleNamespace(rounds=0, dispatches=0)
    if not trace:
        closed_loop(engine, rounds, seconds, window, reservoir)
        return window, reservoir, None, traced

    directory = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(directory)
    annotation = jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN)
    annotation.__enter__()

    def stop(w: Window):
        nonlocal annotation
        annotation.__exit__(None, None, None)
        annotation = None
        traced.rounds, traced.dispatches = w.rounds, w.dispatches
        jax.profiler.stop_trace()

    def on_round(w: Window):
        if annotation is not None and (
                time.perf_counter() - w.t0 >= traffic["trace_seconds"]):
            stop(w)

    closed_loop(engine, rounds, seconds, window, reservoir, on_round)
    if annotation is not None:  # the window ended before the trace did
        stop(window)
    summary = None
    try:
        summary = devtrace.reduce(devtrace.load(directory))
    except LookupError as e:
        log(f"trace: {e}")
    finally:
        if trace_dir is None:
            shutil.rmtree(directory, ignore_errors=True)
    return window, reservoir, summary, traced
