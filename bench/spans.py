"""The device's idle time in a `jax.profiler` trace, put down to the host
phase the serving loop was in: the program's own wall spans.

The program (`repro.obs.tracer`) opens a profiler annotation for each of
its wall spans while a trace records, on the same thread and host plane as
the harness's `window`, `round`, `submit`, `run` and `collect` spans.  Each
nanosecond of the window in which no op ran on the device is charged to
the innermost span covering it on that thread: a program span of
`PROGRAM_SPANS` where there is one, else the harness span, else `outside
rounds`.  Program spans outside the vocabulary (compile-side spans) are
not labels: their time goes to the vocabulary span around them.  The
device's busy time is `devtrace`'s: the union of its `XLA Ops` intervals.

Per bucket dispatch (`engine/dispatch` spans that open in the window),
the idle splits three ways, plus what lies outside every program span:

  * `idle_prepare_us_per_dispatch` — inside `batch/prepare` and
    `batch/launch`: padding, evidence and seed arrays, carry stacking, the
    executable lookup, the enqueue;
  * `idle_unpack_us_per_dispatch` — inside `batch/fetch` and
    `batch/unpack`: the copy back and the per-query results;
  * `idle_engine_us_per_dispatch` — inside `engine/*` spans and outside
    every `batch/*` span: admission, flush, service prediction and pool
    booking, requeueing.

No metric reads this module yet; `PERF.md` (Open questions) names the
edits to `bench/harness.py` and `BENCHMARK.json` that would report them.
"""

from __future__ import annotations

import dataclasses

from bench import devtrace

PROGRAM_SPANS = (
    "engine/admit", "engine/dispatch", "engine/book", "engine/requeue",
    "batch/prepare", "batch/launch", "batch/fetch", "batch/unpack",
)
DISPATCH_SPAN = "engine/dispatch"
OUTSIDE = "outside rounds"
METRICS = {
    "idle_prepare_us_per_dispatch": ("batch/prepare", "batch/launch"),
    "idle_unpack_us_per_dispatch": ("batch/fetch", "batch/unpack"),
    "idle_engine_us_per_dispatch": ("engine/admit", "engine/dispatch",
                                    "engine/book", "engine/requeue"),
}


@dataclasses.dataclass
class SpanIdle:
    idle_s: dict  # {innermost span: device idle seconds}, averaged over devices
    dispatches: int  # engine/dispatch spans opened inside the window
    gaps: list  # [[innermost span at the midpoint, seconds]], longest first

    @property
    def total_s(self) -> float:
        return sum(self.idle_s.values())

    @property
    def in_program_s(self) -> float:
        return sum(v for k, v in self.idle_s.items() if k in PROGRAM_SPANS)


def label(event) -> str:
    """A host event's span name: the part before the `#` a profiler
    annotation may append its arguments with."""
    return event.name.split("#", 1)[0]


def _loop_line(profile):
    """(window start, window end, events of the thread that ran it)."""
    for plane in profile.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            events = list(line.events)
            windows = [e for e in events if label(e) == devtrace.WINDOW_SPAN]
            if windows:
                w0 = min(e.start_ns for e in windows)
                w1 = max(e.start_ns + e.duration_ns for e in windows)
                return w0, w1, events
    raise LookupError("the trace holds no window span")


def partition(spans, w0: float, w1: float) -> list:
    """[(start, end, innermost label)] covering [w0, w1] in order, from
    properly nested spans [(start, end, label)]."""
    segments, stack, cur = [], [], w0

    def emit(t):
        nonlocal cur
        t = min(t, w1)
        if t > cur:
            segments.append((cur, t, stack[-1][2] if stack else OUTSIDE))
            cur = t

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= a:
            emit(stack[-1][1])
            stack.pop()
        emit(a)
        stack.append((a, b, name))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    emit(w1)
    return segments


def _charge(segments, idle) -> dict:
    """Overlap of disjoint sorted idle intervals with the segments."""
    out: dict[str, float] = {}
    i = 0
    for a, b in idle:
        while i < len(segments) and segments[i][1] <= a:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < b:
            s0, s1, name = segments[j]
            overlap = min(b, s1) - max(a, s0)
            if overlap > 0:
                out[name] = out.get(name, 0.0) + overlap
            j += 1
    return out


def _at(segments, t) -> str:
    for a, b, name in segments:
        if a <= t < b:
            return name
    return OUTSIDE


def reduce(profile) -> SpanIdle:
    """The device idle time of a `jax.profiler.ProfileData`'s window by
    innermost span.  Raises LookupError without a window span or device
    ops."""
    w0, w1, events = _loop_line(profile)
    known = set(PROGRAM_SPANS) | set(devtrace.HOST_SPANS)
    spans = [(max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1),
              label(e)) for e in events
             if label(e) in known
             and e.start_ns < w1 and e.start_ns + e.duration_ns > w0]
    segments = partition(spans, w0, w1)
    dispatches = sum(1 for e in events if label(e) == DISPATCH_SPAN
                     and w0 <= e.start_ns < w1)

    devices = [p for p in profile.planes
               if p.name.startswith("/device:TPU:")
               and any(line.name == devtrace.OPS_LINE for line in p.lines)]
    if not devices:
        raise LookupError("the trace holds no TPU op line")
    idle_s: dict[str, float] = {}
    gaps = []
    for n, plane in enumerate(devices):
        ops = [e for line in plane.lines if line.name == devtrace.OPS_LINE
               for e in line.events
               if e.start_ns < w1 and e.start_ns + e.duration_ns > w0]
        merged = devtrace._union(
            (max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1))
            for e in ops)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for name, ns in _charge(segments, idle).items():
            idle_s[name] = idle_s.get(name, 0.0) + ns / 1e9 / len(devices)
        if n == 0:
            gaps = sorted(([_at(segments, (a + b) / 2), (b - a) / 1e9]
                           for a, b in idle), key=lambda g: -g[1])[:10]
    return SpanIdle(idle_s=idle_s, dispatches=dispatches, gaps=gaps)


def per_dispatch(s: SpanIdle) -> dict:
    """{metric: device idle us per dispatch} for `METRICS`, plus
    `idle_outside_us_per_dispatch` (outside every program span); empty
    when the window holds no `engine/dispatch` span."""
    if s.dispatches <= 0:
        return {}
    out = {m: sum(s.idle_s.get(n, 0.0) for n in names) * 1e6 / s.dispatches
           for m, names in METRICS.items()}
    out["idle_outside_us_per_dispatch"] = (
        (s.total_s - s.in_program_s) * 1e6 / s.dispatches)
    return out
