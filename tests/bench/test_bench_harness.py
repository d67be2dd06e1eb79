"""The chip benchmark's harness (`bench/`): finding cells by name, query
generation, site-update counting, closed-loop arithmetic, the reduction of
profiler traces, and the refusal to run without a TPU."""

from __future__ import annotations

import gzip
import hashlib
import json
import os
import pathlib
import re
import subprocess
import sys
import time
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import devtrace, generator, harness, models, spec  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCHMARK = spec.load_benchmark(ROOT)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def _cell(name):
    cell = spec.load_cell(ROOT, name)
    return cell, models.build(cell.config)


# -- BENCHMARK.json: names, files and readers ------------------------------


def test_benchmark_json_keys_and_names():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    metrics = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]
    names = ([c["name"] for c in BENCHMARK["configs"]] + CELLS
             + [m["name"] for m in metrics]
             + [w["traffic"] for w in BENCHMARK["workloads"]])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert len(set(CELLS)) == len(CELLS)
    assert "setup_s" in {m["name"] for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= set(CELLS)
    for path in BENCHMARK["paths"]:
        assert (ROOT / path).is_dir()
    assert 1 <= BENCHMARK["run_seconds"] <= 51


@pytest.mark.parametrize("name", CELLS)
def test_cells_found_by_name(name):
    cell = spec.load_cell(ROOT, name)
    w = next(w for w in BENCHMARK["workloads"] if w["name"] == name)
    assert cell.chips == w["chips"] == 1
    assert cell.config["name"] == w["config"]
    names = {m["name"] for m in cell.end_to_end}
    assert names <= {"queries_per_s", "query_p95_ms", "setup_s"}
    assert "setup_s" in names and len(names) >= 2
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    conf = next(c for c in BENCHMARK["configs"] if c["name"] == w["config"])
    assert any(conf["file"].startswith(p + "/") for p in BENCHMARK["paths"])
    for tenant in cell.traffic["tenants"]:
        assert tenant["model"] in cell.config["models"]


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        spec.load_cell(ROOT, "no-such.cell")


# -- query streams and site updates ----------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_stream(name):
    cell, plain = _cell(name)
    seed = 2**31 + 12345

    def draw(s):
        stream = generator.Stream(cell.traffic, cell.config, plain, s,
                                  generator.WINDOW)
        return stream.rounds(3)

    a, b, c = draw(seed), draw(seed), draw(seed + 1)
    flat = [q for r in a for q in r]
    assert len(a[0]) == len(generator.clients(cell.traffic))
    for qa, qb in zip(flat, [q for r in b for q in r]):
        assert (qa.model, qa.seed, qa.evidence) == (qb.model, qb.seed,
                                                   qb.evidence)
        if qa.image is not None:
            assert np.array_equal(qa.image, qb.image)
    other = [q for r in c for q in r]
    # another seed: the same models, sizes and observed nodes, new contents
    assert [q.model for q in other] == [q.model for q in flat]
    assert [q.seed for q in other] != [q.seed for q in flat]
    for qa, qc in zip(flat, other):
        if qa.evidence is not None:
            assert sorted(qa.evidence) == sorted(qc.evidence)
    warm = generator.Stream(cell.traffic, cell.config, plain, seed,
                            generator.WARMUP).round()
    assert {q.qid for q in warm}.isdisjoint(q.qid for q in flat)


def test_mrf_stream_is_pinned():
    """`tiny.mrf`'s observations and chain seeds, bit for bit: a change to
    how `bench/images/` draws them shows here."""
    cell = spec.load_cell(DATA / "cells", "tiny.mrf")
    plain = models.build(cell.config)
    h = hashlib.sha256()
    for stream in (generator.WINDOW, generator.WARMUP):
        for r in generator.Stream(cell.traffic, cell.config, plain,
                                  2**31 + 977, stream).rounds(3):
            for q in r:
                h.update(f"{q.qid} {q.model} {q.seed} {q.n_chains} "
                         f"{q.n_iters};".encode())
                h.update(q.image.astype("<i4").tobytes())
    assert h.hexdigest() == (
        "6e87a2cc98b9e3918632e27f6369e6fb616cf60cf62b5920dfa73204799d7fdc")


@pytest.mark.parametrize("image", [np.zeros((8, 8), np.int64),
                                   np.zeros((8, 7), np.int32)])
def test_observation_of_the_wrong_type_or_shape_is_refused(image):
    entry = {"height": 8, "width": 8, "labels": 3}
    with pytest.raises(ValueError, match="int32"):
        generator.observation(lambda *a: image, entry, {}, seed=0)


def test_site_updates_counted_from_traffic():
    cell, plain = _cell("bnlearn.long")
    # 4 clients x 32 chains x 200 sweeps on hepar2 (70 nodes, 17 observed)
    # and on pigs (441 nodes, 110 observed)
    assert generator.site_updates(cell.traffic, cell.config, plain) == (
        4 * 32 * 200 * (70 - 17) + 4 * 32 * 200 * (441 - 110))
    zipf = json.loads((ROOT / "bench" / "traffic" / "zipf-short.json")
                      .read_text())
    # 8 chains x 40 sweeps; clients 7/3/2/2/1/1 on survey (6 nodes, 1
    # observed), cancer (5, 1), asia (8, 2), sachs (11, 2), insurance
    # (27, 6), alarm (37, 9)
    free = 7 * 5 + 3 * 4 + 2 * 6 + 2 * 9 + 1 * 21 + 1 * 28
    assert generator.site_updates(zipf, cell.config, plain) == 8 * 40 * free


BN_CONFIG = json.loads(
    (ROOT / "bench" / "configs" / "bnlearn-replicas.json").read_text())


@pytest.mark.parametrize("name", sorted(BN_CONFIG["models"]))
def test_replicas_follow_their_published_node_counts(name):
    want = BN_CONFIG["models"][name]
    m = models.build({"kind": "bn", "models": {name: want}})[name]
    in_degree = [len(p) for p in m["parents"]]
    assert len(m["cards"]) == want["n_nodes"]
    assert sum(in_degree) == want["n_arcs"]
    assert max(in_degree) == want["max_in_degree"]
    assert all(p < i for i, ps in enumerate(m["parents"]) for p in ps)
    assert set(m["cards"]) <= set(want["arities"])
    for i, (cpt, ps) in enumerate(zip(m["cpts"], m["parents"])):
        assert cpt.shape == tuple(int(m["cards"][p]) for p in [*ps, i])
        assert np.allclose(cpt.sum(-1), 1.0)


def test_replica_arcs_must_fit_a_dag():
    with pytest.raises(ValueError, match="no DAG"):
        models.random_bayesnet(5, 11, 2, [2], seed=0)


# -- closed-loop arithmetic ------------------------------------------------


class _FakeEngine:
    """Answers every submitted query after `delay` seconds."""

    def __init__(self, delay):
        self.delay, self.queue = delay, []
        self.metrics = types.SimpleNamespace(batch_records=[])

    def submit(self, queries):
        self.queue = list(queries)

    def run(self):
        time.sleep(self.delay)
        rec = types.SimpleNamespace(n_real=len(self.queue), n_padded=4)
        self.metrics = types.SimpleNamespace(batch_records=[rec])
        out = {q.qid: types.SimpleNamespace(final_state=np.zeros(2),
                                            marginals=None)
               for q in self.queue[:-1]}  # the last query is never answered
        self.queue = []
        return out


def test_closed_loop_counts_all_queries_over_all_time():
    specs = [[types.SimpleNamespace(qid=3 * r + i) for i in range(3)]
             for r in range(100)]
    rounds = [(s, s) for s in specs]
    window = harness.Window()
    reservoir = harness.Reservoir(4, seed=1)
    harness.closed_loop(_FakeEngine(0.02), rounds, 0.2, window, reservoir)
    assert window.rounds >= 1 and window.seconds >= 0.2
    assert window.submitted == 3 * window.rounds
    assert window.answered == 2 * window.rounds
    assert window.lost == window.rounds
    assert window.seconds >= 0.02 * window.rounds
    assert harness.queries_per_s(window) == window.answered / window.seconds
    assert all(lat >= 0.02 for lat in window.latencies)
    assert (window.n_real, window.n_padded) == (3 * window.rounds,
                                                4 * window.rounds)
    assert len(reservoir.items) == 4 and reservoir.n == window.answered


def test_stream_must_outlast_the_window():
    s = [types.SimpleNamespace(qid=0)]
    with pytest.raises(RuntimeError, match="ran out"):
        harness.closed_loop(_FakeEngine(0.0), [(s, s)], 5.0, harness.Window())


def test_p95_over_every_latency():
    lat = [i / 1000 for i in range(1, 101)]
    assert harness.p95_ms(lat) == pytest.approx(95.05)
    assert harness.p95_ms([0.5] * 10) == pytest.approx(500.0)


def test_reservoir_is_seeded_and_uniform_sized():
    def sample(seed):
        r = harness.Reservoir(5, seed)
        for i in range(1000):
            r.offer(i)
        return r.items

    assert sample(7) == sample(7) != sample(8)
    assert len(set(sample(7))) == 5


# -- trace reduction -------------------------------------------------------

_SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 30000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "while.1" } }
  event_metadata { key: 2 value { id: 2
    name: "%closed_call.3 = s32[4,32]{1,0} custom-call(s32[4]{0} %p.1)" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.7" } }
  event_metadata { key: 4 value { id: 4 name: "jit__bn_bucket" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 8500000 }
    events { metadata_id: 5 offset_ps: 9500000 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "round" } }
  event_metadata { key: 3 value { id: 3 name: "submit" } }
  event_metadata { key: 4 value { id: 4 name: "run" } }
  event_metadata { key: 5 value { id: 5 name: "collect" } }
}
"""


def test_reduction_of_a_synthetic_trace():
    """Window 10 us; busy [1, 5) and [7, 8) us; the while loop holds the
    kernel (a custom call by its opcode), so its self time is 2 us; the
    fusion is XLA's; an op after the window is ignored."""
    from jax.profiler import ProfileData

    s = devtrace.reduce(ProfileData.from_text_proto(_SYNTHETIC))
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx(5e-6)
    assert s.idle_s == pytest.approx(5e-6)
    assert s.kernel_s == pytest.approx(2e-6)
    assert s.xla_s == pytest.approx(3e-6)
    assert dict(map(tuple, s.top_ops)) == pytest.approx(
        {"while.1": 2e-6, "%closed_call.3 = s32[4,32]": 2e-6,
         "fusion.7": 1e-6})
    # idle [0,1) us in submit, [5,7) in run, [8,10) split: midpoint 9 us
    # is in run
    assert s.gaps == [["run", pytest.approx(2e-6)],
                      ["run", pytest.approx(2e-6)],
                      ["submit", pytest.approx(1e-6)]]


def test_trace_without_window_or_device_is_refused():
    from jax.profiler import ProfileData

    no_window = _SYNTHETIC.replace('name: "window"', 'name: "other"')
    with pytest.raises(LookupError, match="window"):
        devtrace.reduce(ProfileData.from_text_proto(no_window))
    no_device = _SYNTHETIC.replace('"/device:TPU:0"', '"/device:CPU:0"')
    with pytest.raises(LookupError, match="TPU"):
        devtrace.reduce(ProfileData.from_text_proto(no_device))


def _chip_trace():
    from jax.profiler import ProfileData

    packed = (DATA / "mrf_stream.xplane.pb.gz").read_bytes()
    return ProfileData.from_serialized_xspace(gzip.decompress(packed))


def test_reduction_of_a_chip_trace():
    """A trace recorded on one TPU v5e chip from `mrf-denoise.stream`
    (`bench/run.py --trace 1 --trace-dir`, 2 s of rounds); the expected
    numbers are this reduction's, kept so a change to it shows."""
    expected = json.loads((DATA / "mrf_stream.summary.json").read_text())
    s = devtrace.reduce(_chip_trace())
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.kernel_s > 0 and s.xla_s > 0
    assert s.kernel_s + s.xla_s == pytest.approx(s.busy_s, rel=1e-6)
    assert {g[0] for g in s.gaps} <= set(devtrace.HOST_SPANS) | {
        "outside rounds"}
    got = {"window_s": s.window_s, "busy_s": s.busy_s,
           "kernel_s": s.kernel_s, "xla_s": s.xla_s}
    assert got == pytest.approx(expected, rel=1e-9)


def test_metric_readers_on_a_chip_trace():
    s = devtrace.reduce(_chip_trace())
    ctx = types.SimpleNamespace(
        kind="mrf", trace=s, traced_site_updates=10**6, traced_dispatches=10,
        n_real=8, n_padded=8, compiles_in_window=0)
    read = {n: spec.metric_reader(n)(ctx) for n in (
        "device_idle_share", "mrf_kernel_ns_per_update", "xla_ns_per_update",
        "host_gap_us_per_dispatch", "pad_efficiency", "compiles_in_window",
        "bn_kernel_ns_per_update")}
    assert read["device_idle_share"] == pytest.approx(
        100 * (1 - s.busy_s / s.window_s))
    assert read["mrf_kernel_ns_per_update"] == pytest.approx(s.kernel_s * 1e3)
    assert read["xla_ns_per_update"] == pytest.approx(s.xla_s * 1e3)
    assert read["host_gap_us_per_dispatch"] == pytest.approx(
        s.idle_s * 1e6 / 10)
    assert read["pad_efficiency"] == 100.0
    assert read["compiles_in_window"] == 0
    assert read["bn_kernel_ns_per_update"] is None  # not a BN cell
    ctx.trace = None
    assert spec.metric_reader("device_idle_share")(ctx) is None


# -- no accelerator, no result ---------------------------------------------


def test_run_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    assert not [line for line in proc.stdout.splitlines()
                if line.startswith("{")]
