"""Roofline-term derivation from a compiled dry-run artifact.

Per (arch x shape x mesh) cell:

    compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
    memory term     = HLO_bytes / (chips x HBM_bw)
    collective term = collective_bytes / (chips x link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``;
collective_bytes is parsed out of the post-SPMD optimized HLO
(``compiled.as_text()``) by summing the result sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute.

Hardware constants come from `PEAKS`, one row per `device_kind` with its
published source; a kind that is not in the table raises.  CPU-side static
sweeps name the chip they target (`TARGET_KIND`) explicitly.

Known-limits note (documented, accounted for in the tables): XLA's HLO cost
analysis reports a while-loop body ONCE, not multiplied by its trip count.
Our layer stack is a scan over n_super superblocks, so we scale loop-body
costs by the known trip counts, which we recover by matching
``while`` trip counts in the HLO (see `_scan_correction`).
"""

from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    ici_bw: float  # ICI bytes/s per link, one direction
    source: str


# Published per-chip peaks keyed by `jax.Device.device_kind`.
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12,
        hbm_bw=819e9,
        # 1,600 Gbit/s chip-to-chip interconnect over the chip's four links
        ici_bw=50e9,
        source='Google Cloud documentation, "TPU v5e" (system architecture)',
    ),
}

# The chip the kernels are written for: what CPU-side static sweeps and
# rehearsals (no chip attached) price their programs against.
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of one chip kind; an unknown kind raises rather
    than borrowing another chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add a "
            "sourced row to launch/roofline.PEAKS"
        ) from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Sum byte sizes of every array shape in an HLO type string (handles
    tuples like (f32[8,16], f32[8,16]))."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_op: dict[str, int]
    count_by_op: dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum result sizes of collective ops in optimized HLO, scaling ops that
    live inside while-loop bodies by the loop trip count."""
    bytes_by: dict[str, int] = {c: 0 for c in _COLLECTIVES}
    count_by: dict[str, int] = {c: 0 for c in _COLLECTIVES}
    # computation name -> trip count for scan bodies
    trips = _body_trip_counts(hlo_text)
    current_comp = ""
    for line in hlo_text.splitlines():
        comp = re.match(r"\s*%?([\w\.\-]+)\s*\([^)]*\)\s*->", line)
        if line.strip().startswith(("ENTRY", "%")) and "{" in line and "->" in line:
            m = re.search(r"%?([\w\.\-]+)\s*\(", line)
            if m:
                current_comp = m.group(1)
        for op in _COLLECTIVES:
            # match "= <type> <op>(" and "<op>-start(" variants
            if re.search(rf"=\s+[^=]*\b{op}(-start)?\(", line):
                lhs = line.split("=", 1)[1]
                type_part = lhs.strip().split(op)[0]
                b = _shape_bytes(type_part)
                mult = trips.get(current_comp, 1)
                bytes_by[op] += b * mult
                count_by[op] += mult
    return CollectiveStats(bytes_by, count_by)


def _body_trip_counts(hlo_text: str) -> dict[str, int]:
    """Best-effort: map while-body computation names to trip counts.

    XLA scan loops carry an iteration counter compared against a constant;
    we find `while` ops, their body names, and look for the constant bound
    in the loop condition computation."""
    # condition computations: name -> bound
    cond_bounds: dict[str, int] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = re.match(r"%?([\w\.\-]+)\s*\([^)]*\)\s*->\s*pred\[\]", line.strip())
        if m:
            cur = m.group(1)
        if cur and ("compare" in line and "LT" in line):
            consts = re.findall(r"constant\((\d+)\)", line)
        if cur and "constant(" in line:
            c = re.findall(r"constant\((\d+)\)", line)
            if c:
                cond_bounds.setdefault(cur, int(c[-1]))
        if line.strip() == "}":
            cur = None
    trips: dict[str, int] = {}
    for m in re.finditer(
        r"while\(.*?\),\s*condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)",
        hlo_text,
    ):
        cond, body = m.group(1), m.group(2)
        if cond in cond_bounds:
            trips[body] = max(1, cond_bounds[cond])
    return trips


@dataclasses.dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    collective_bytes: float
    n_chips: int
    device_kind: str
    model_flops: float = 0.0

    @property
    def peaks(self) -> ChipPeaks:
        return peaks(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops / (self.n_chips * self.peaks.flops)

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / (self.n_chips * self.peaks.hbm_bw)

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / (self.n_chips * self.peaks.ici_bw)

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "n_chips": self.n_chips,
            "device_kind": self.device_kind,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
        }


def model_flops(cfg, cell_kind: str, seq: int, batch: int) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train (fwd+bwd), 2·N·D prefill,
    2·N per token decode; N = active params (MoE-aware)."""
    n = cfg.n_active_params()
    if cell_kind == "train":
        return 6.0 * n * seq * batch
    if cell_kind == "prefill":
        return 2.0 * n * seq * batch
    return 2.0 * n * batch  # decode: one token per sequence
