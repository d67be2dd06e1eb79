"""The inpainting energy's `correct`, at a size the CPU can hold: a tiny
Penguin-like cell (truncated-quadratic smoothness, a masked hole, 32
gray levels on a 13 x 10 grid) built in a temporary directory, served
sliced and checked against `bench/reference/mrf_inpaint.py`.
The served answers equal the reference; the control (4-bit LUT weights)
and a program that ignores the truncation do not."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import control, harness  # noqa: E402

CELLS = pathlib.Path(__file__).resolve().parent / "data" / "cells"
SEED = 2**31 + 977
CELL = "tiny.inpaint"


@pytest.fixture(scope="module")
def cells(tmp_path_factory) -> pathlib.Path:
    root = tmp_path_factory.mktemp("cells")
    shutil.copytree(CELLS, root, dirs_exist_ok=True)
    config = json.loads(
        (ROOT / "bench/configs/middlebury-penguin.json").read_text())
    config["name"] = "tiny-inpaint"
    config["models"] = {"grid": dict(
        config["models"]["penguin"], height=13, width=10, labels=32,
        theta=0.5)}
    # unfused, so the CPU compiles no interpreted kernel: the program's
    # routes are held to one another in tests/test_mrf_inpaint.py
    config["engine"] = {"fused": False, "max_batch": 4, "slice_iters": 2}
    (root / "bench/configs/tiny-inpaint.json").write_text(json.dumps(config))
    traffic = json.loads((ROOT / "bench/traffic/inpaint.json").read_text())
    traffic.update(tenants=[{"model": "grid", "clients": 2}],
                   query={"n_chains": 2, "n_iters": 4, "sampler": "lut_ky"},
                   image_pool=2, trace_seconds=0.2)
    (root / "bench/traffic/tiny-inpaint.json").write_text(
        json.dumps(traffic))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-inpaint", "source": config["source"],
        "file": "bench/configs/tiny-inpaint.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-inpaint",
                               "traffic": "tiny-inpaint", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(cells: pathlib.Path) -> dict:
    return harness.run_cell(cells, CELL, SEED, 0.3, False,
                            time.perf_counter(), require_tpu=False,
                            log=lambda s: None)


def test_served_answers_equal_the_reference(cells):
    out = _run(cells)
    assert out["correct"], out["checks"]
    assert out["checks"]["mismatched_queries"]["value"] == 0
    assert out["checks"]["checked_queries"]["value"] == 4


def test_control_is_not_correct(cells):
    got = control.control_mismatches(cells, CELL, SEED)
    assert got["checked_queries"] == 4
    assert got["mismatched_queries"] > 0


def test_ignoring_the_truncation_is_not_correct(cells, monkeypatch):
    from repro.core import mrf

    energy = mrf.log_potentials

    def untruncated(*a, truncation=None, **k):
        return energy(*a, truncation=None if truncation is None else 1e9,
                      **k)

    monkeypatch.setattr(mrf, "log_potentials", untruncated)
    jax.clear_caches()  # the fault acts when the executables are traced
    try:
        out = _run(cells)
    finally:
        jax.clear_caches()
    assert not out["correct"]
    assert out["checks"]["mismatched_queries"]["value"] > 0
