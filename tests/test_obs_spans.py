"""Wall spans of the serving path (`repro.obs.tracer.span`): on the
`jax.profiler` timeline whenever a trace records, in the ring buffer when
it is enabled, and the shared null span when neither is on.

  * a CPU profiler trace of a tiny `Engine.run` (two buckets, one sliced
    query) holds every span of the vocabulary on its host plane, each
    `batch/*` span inside an `engine/dispatch` of the same number, and one
    `engine/dispatch` per `BatchRecord`;
  * with tracing off and no profiler, every span site gets `NULL_SPAN`;
  * a live span inherits its enclosing span's `dispatch` number, and
    profiler-only spans leave no ring-buffer events.
"""

import glob
import os

import jax
import pytest

from repro import obs
from repro.compile import clear_program_cache
from repro.core.graphs import bn_repository_replica
from repro.obs import tracer
from repro.obs.tracer import NULL_SPAN
from repro.runtime import Engine, EngineConfig, Query

SPANS = ("engine/admit", "engine/dispatch", "batch/prepare", "batch/launch",
         "batch/fetch", "batch/unpack", "engine/book", "engine/requeue")
BATCH = tuple(n for n in SPANS if n.startswith("batch/"))


@pytest.fixture(autouse=True)
def _obs_off():
    obs.disable()
    clear_program_cache()
    yield
    obs.disable()
    clear_program_cache()


def _engine():
    models = {"survey": bn_repository_replica("survey"),
              "cancer": bn_repository_replica("cancer")}
    return Engine(models, EngineConfig(pad_sizes=(2,), max_batch=2,
                                       slice_iters=4))


def _queries():
    """Two buckets (one per model); the survey query runs in two slices."""
    return [
        Query(qid=0, model="survey", evidence={0: 1}, n_chains=2, n_iters=8,
              burn_in=0, seed=1),
        Query(qid=1, model="cancer", evidence={0: 0}, n_chains=2, n_iters=4,
              burn_in=0, seed=2, arrival_s=1e-4),
    ]


def _serve(engine):
    engine.submit(_queries())
    return engine.run()


def _host_events(directory):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                        recursive=True)
    profile = ProfileData.from_file(path)
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out += [(line.name, e.name.split("#", 1)[0], e.start_ns,
                         e.start_ns + e.duration_ns, dict(e.stats))
                        for e in line.events]
    return out


def test_engine_spans_land_on_the_profiler_host_plane(tmp_path):
    engine = _engine()
    _serve(engine)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        results = _serve(engine)
    finally:
        jax.profiler.stop_trace()
    assert sorted(results) == [0, 1]
    assert tracer.get() is None  # the ring buffer stayed off
    events = [e for e in _host_events(str(tmp_path)) if e[1] in SPANS]
    assert {e[1] for e in events} == set(SPANS)
    dispatches = [e for e in events if e[1] == "engine/dispatch"]
    records = engine.metrics.batch_records
    assert len(dispatches) == len(records) == 3
    numbers = [e[4]["dispatch"] for e in dispatches]
    assert len(set(numbers)) == 3
    for e, rec in zip(sorted(dispatches, key=lambda e: e[2]), records):
        assert e[4]["model"] == rec.model and e[4]["route"] == rec.route
        assert (e[4]["n_real"], e[4]["n_padded"]) == (rec.n_real,
                                                      rec.n_padded)
    assert sorted(e[4]["resumed"] for e in dispatches) == [0, 0, 1]
    for line, name, t0, t1, stats in events:
        if name in BATCH or name in ("engine/book", "engine/requeue"):
            parents = [d for d in dispatches if d[0] == line
                       and d[2] <= t0 and t1 <= d[3]]
            assert len(parents) == 1, name
            assert stats["dispatch"] == parents[0][4]["dispatch"]
    for name in BATCH + ("engine/book", "engine/requeue"):
        assert sum(e[1] == name for e in events) == 3


def test_span_sites_return_the_null_span_with_both_off(monkeypatch):
    engine = _engine()
    _serve(engine)
    assert not tracer.enabled() and not tracer.profiling()
    seen = []
    real = tracer.span

    def spy(name, *args, **kwargs):
        s = real(name, *args, **kwargs)
        seen.append((name, s))
        return s

    monkeypatch.setattr(tracer, "span", spy)
    _serve(engine)
    assert {name for name, _ in seen} == set(SPANS)
    assert all(s is NULL_SPAN for _, s in seen)


def test_ring_buffer_spans_carry_the_dispatch_number():
    engine = _engine()
    tr = obs.enable()
    _serve(engine)
    obs.disable()
    spans = [e for e in tr.events if e.kind == "span" and e.name in SPANS]
    by_dispatch = {}
    for e in spans:
        by_dispatch.setdefault(e.args["dispatch"], []).append(e.name)
    dispatched = [n for n, names in by_dispatch.items()
                  if "engine/dispatch" in names]
    assert len(dispatched) == len(engine.metrics.batch_records) == 3
    for n in dispatched:
        assert set(by_dispatch[n]) >= set(SPANS) - {"engine/admit"}


def test_live_spans_inherit_the_dispatch_number():
    tr = obs.enable()
    with tracer.span("outer", dispatch=7):
        with tracer.span("inner"):
            pass
        with tracer.span("own", dispatch=8):
            pass
    with tracer.span("after"):
        pass
    args = {e.name: e.args for e in tr.events}
    assert args["inner"] == {"dispatch": 7}
    assert args["own"] == {"dispatch": 8}
    assert args["after"] == {}


def test_profiler_only_spans_are_live_and_leave_no_ring_events(tmp_path):
    assert tracer.span("x") is NULL_SPAN
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracer.profiling()
        with tracer.span("probe", dispatch=3) as s:
            assert s is not NULL_SPAN
            s.set(extra=5)
    finally:
        jax.profiler.stop_trace()
    assert not tracer.profiling() and tracer.get() is None
    assert tracer.span("x") is NULL_SPAN
    probes = [e for e in _host_events(str(tmp_path)) if e[1] == "probe"]
    assert len(probes) == 1
    assert probes[0][4] == {"dispatch": 3, "extra": 5}
