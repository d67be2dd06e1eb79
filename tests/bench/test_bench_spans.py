"""`bench/spans.py`: the device's idle time put down to the program's wall
spans, on a synthetic trace and on a recorded chip trace."""

from __future__ import annotations

import gzip
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import devtrace, spans  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _event(meta: int, t0_us: float, t1_us: float) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {round(t0_us * 1e6)} "
            f"duration_ps: {round((t1_us - t0_us) * 1e6)} }}")


# host spans on the loop's thread, in microseconds
_LOOP = [
    ("window", 0, 10), ("round", 0, 10), ("submit", 0, 0.5),
    ("run", 0.5, 9.5), ("engine/admit", 0.6, 1.2),
    ("engine/dispatch#dispatch=0,model=m#", 2, 8.8),
    ("batch/prepare", 2, 4), ("clamp_lowering", 3.2, 3.8),
    ("batch/launch", 4, 5), ("batch/fetch", 5, 7), ("batch/unpack", 7, 7.5),
    ("engine/book", 7.5, 8.2), ("engine/requeue", 8.2, 8.5),
    ("collect", 9.5, 10),
]


def _synthetic() -> str:
    names = sorted({n for n, _, _ in _LOOP})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for n, i in ids.items())
    loop = "\n".join(_event(ids[n], a, b) for n, a, b in _LOOP)
    other = _event(ids["batch/prepare"], 0, 10)
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {_event(1, 1, 3)}
    {_event(2, 5.5, 8.6)}
    {_event(1, 12, 13)}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }}
  event_metadata {{ key: 2 value {{ id: 2
    name: "%bn_gibbs_kernel.3 = s32[4,32]{{1,0}} custom-call(s32[4]{{0}} %p)" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
    {loop}
  }}
  lines {{ id: 2 name: "another thread" timestamp_ns: 0
    {other}
  }}
  {meta}
}}
"""


def _profile(text):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text)


def test_idle_split_by_overlap_and_charged_to_the_innermost_span():
    """Busy [1, 3) and [5.5, 8.6) us of a 10 us window.  The gap [3, 5.5)
    straddles prepare, launch and fetch and is split by overlap; the
    compile-side span inside prepare is no label; the thread that did
    not run the loop is ignored; idle outside program spans goes to the
    harness span around it."""
    s = spans.reduce(_profile(_synthetic()))
    us = {k: v * 1e6 for k, v in s.idle_s.items()}
    assert us == pytest.approx({
        "submit": 0.5, "run": 0.1 + 0.7, "engine/admit": 0.4,
        "batch/prepare": 1.0, "batch/launch": 1.0, "batch/fetch": 0.5,
        "engine/dispatch": 0.2, "collect": 0.5})
    assert s.dispatches == 1
    assert s.total_s == pytest.approx(4.9e-6)
    assert s.in_program_s == pytest.approx(3.1e-6)
    assert [g[0] for g in s.gaps] == ["batch/launch", "run", "run"]
    assert [g[1] for g in s.gaps] == pytest.approx([2.5e-6, 1.4e-6, 1e-6])


def test_three_metrics_and_the_rest_add_up_to_the_idle_time():
    profile = _profile(_synthetic())
    s = spans.reduce(profile)
    got = spans.per_dispatch(s)
    assert got == pytest.approx({
        "idle_prepare_us_per_dispatch": 2.0,
        "idle_unpack_us_per_dispatch": 0.5,
        "idle_engine_us_per_dispatch": 0.6,
        "idle_outside_us_per_dispatch": 1.8,
    })
    # the same idle time as the trace reduction the benchmark reports
    idle_us = devtrace.reduce(profile).idle_s * 1e6
    assert sum(got.values()) == pytest.approx(idle_us)


def test_partition_nests_and_covers_the_window():
    segs = spans.partition(
        [(0, 10, "run"), (2, 6, "engine/dispatch"), (3, 4, "batch/fetch"),
         (8, 12, "collect")], 0, 11)
    assert segs == [(0, 2, "run"), (2, 3, "engine/dispatch"),
                    (3, 4, "batch/fetch"), (4, 6, "engine/dispatch"),
                    (6, 8, "run"), (8, 11, "collect")]


def test_a_trace_without_program_spans_has_no_split():
    """A trace of a program without the spans (the recorded MRF chip
    trace): all idle time lies outside program spans, the total is the
    benchmark's, and no per-dispatch split is reported."""
    from jax.profiler import ProfileData

    packed = (DATA / "mrf_stream.xplane.pb.gz").read_bytes()
    profile = ProfileData.from_serialized_xspace(gzip.decompress(packed))
    s = spans.reduce(profile)
    assert s.dispatches == 0 and s.in_program_s == 0
    assert set(s.idle_s) <= set(devtrace.HOST_SPANS) | {spans.OUTSIDE}
    assert s.total_s == pytest.approx(devtrace.reduce(profile).idle_s,
                                      rel=1e-9)
    assert spans.per_dispatch(s) == {}


def test_a_trace_without_a_window_is_refused():
    with pytest.raises(LookupError, match="window"):
        spans.reduce(_profile(_synthetic().replace('"window"', '"other"')))
