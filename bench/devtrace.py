"""Reduction of a `jax.profiler` trace to device time, kernel time and the
host's share of the device's idle time.

The window is the harness's own `window` span on the host.  On each TPU
plane, the ops of the `XLA Ops` line are the device's work: busy time is
the union of their intervals inside the window, idle time the rest.  An op
is a Mosaic kernel when its HLO opcode is a custom call; every other op
is XLA's.  Ops nest (a loop holds its body), so kernel and XLA time are
self times: an op's duration less the part its children cover.  Each idle
gap is named by the innermost harness span (`round`, `submit`, `run`,
`collect`) the host was in at the gap's midpoint.
"""

from __future__ import annotations

import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "window"
HOST_SPANS = ("round", "submit", "run", "collect")


@dataclasses.dataclass
class Summary:
    n_devices: int
    window_s: float
    busy_s: float  # per device, averaged over devices
    kernel_s: float  # self time of Mosaic custom calls, summed over devices
    xla_s: float  # self time of every other op, summed over devices
    top_ops: list  # [[name, seconds]], most time first
    gaps: list  # [[host span, seconds]], longest first (first device)

    @property
    def idle_s(self) -> float:
        return self.window_s - self.busy_s


def is_kernel(event) -> bool:
    """Whether a device op is a Mosaic (Pallas) kernel: a custom call, by
    its opcode in the HLO text the TPU trace names each op with
    (`%name = type custom-call(operands), custom_call_target=...`)."""
    return " custom-call(" in event.name


def short_name(event) -> str:
    """An op's name and result type, without layouts and operands."""
    return event.name.split("{", 1)[0].split("(%", 1)[0].strip()


def _self_times(events):
    """[(event, self ns)] for events of one line, nesting resolved."""
    events = sorted(events, key=lambda e: (e.start_ns, -e.duration_ns))
    out, stack = [], []  # stack of [end_ns, index into out]
    for e in events:
        while stack and stack[-1][0] <= e.start_ns:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= e.duration_ns
        out.append([e, float(e.duration_ns)])
        stack.append([e.start_ns + e.duration_ns, len(out) - 1])
    return out


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def reduce(profile) -> Summary:
    """Summary of a `jax.profiler.ProfileData`.  Raises LookupError when
    the trace holds no window span or no device ops."""
    host = []
    for plane in profile.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                host += [e for e in line.events
                         if e.name in HOST_SPANS or e.name == WINDOW_SPAN]
    windows = [e for e in host if e.name == WINDOW_SPAN]
    if not windows:
        raise LookupError("the trace holds no window span")
    w0 = min(e.start_ns for e in windows)
    w1 = max(e.start_ns + e.duration_ns for e in windows)
    spans = [e for e in host if e.name in HOST_SPANS]

    devices = [p for p in profile.planes
               if p.name.startswith("/device:TPU:")
               and any(line.name == OPS_LINE for line in p.lines)]
    if not devices:
        raise LookupError("the trace holds no TPU op line")
    busy = kernel = xla = 0.0
    per_op: dict[str, float] = {}
    gaps = []
    for n, plane in enumerate(devices):
        ops = [e for line in plane.lines if line.name == OPS_LINE
               for e in line.events
               if e.start_ns < w1 and e.start_ns + e.duration_ns > w0]
        for e, self_ns in _self_times(ops):
            if is_kernel(e):
                kernel += self_ns
            else:
                xla += self_ns
            name = short_name(e)
            per_op[name] = per_op.get(name, 0.0) + self_ns
        merged = _union(
            (max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1))
            for e in ops)
        busy += sum(b - a for a, b in merged)
        if n == 0:
            edges = [w0] + [x for ab in merged for x in ab] + [w1]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append([_host_label(spans, (a + b) / 2), (b - a) / 1e9])
    if not per_op:
        raise LookupError("no device op ran inside the window")
    gaps.sort(key=lambda g: -g[1])
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return Summary(
        n_devices=len(devices), window_s=(w1 - w0) / 1e9,
        busy_s=busy / 1e9 / len(devices), kernel_s=kernel / 1e9,
        xla_s=xla / 1e9, top_ops=[[k, v / 1e9] for k, v in top],
        gaps=gaps[:10],
    )


def _host_label(spans, t_ns) -> str:
    inside = [e for e in spans if e.start_ns <= t_ns < e.start_ns + e.duration_ns]
    if not inside:
        return "outside rounds"
    return min(inside, key=lambda e: e.duration_ns).name


def load(directory: str):
    """The ProfileData of the one `.xplane.pb` a trace directory holds."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise LookupError(f"expected one .xplane.pb under {directory}, "
                          f"found {len(paths)}")
    return ProfileData.from_file(paths[0])
