"""What decides the benchmark's `correct`, at a size the CPU can hold: the
plain reference agrees with the served answers bit for bit, the control
(the reference one precision below, in the program's place) does not, and
a run with the timed path broken underneath comes out not correct for each
fault a one-chip cell can have.  (A four-chip fault, the exchange between
chips left out, has no cell yet.)  An MRF energy the program or the
reference cannot build is refused before anything is compared."""

from __future__ import annotations

import dataclasses
import json
import math
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
# the Potts data term, a quadratic one, and a BN
WORKLOADS = ["tiny.bn", "tiny.mrf", "tiny.mrf-quadratic"]


def _run(workload: str, seconds: float = 0.3) -> dict:
    return harness.run_cell(CELLS, workload, SEED, seconds, False,
                            time.perf_counter(), require_tpu=False,
                            log=lambda s: None)


@pytest.fixture
def fresh_jit():
    """Planted faults act when the bucket executables are traced, so each
    fault test traces anew and leaves no faulty executable behind."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("workload", WORKLOADS)
def test_served_answers_equal_the_reference(workload):
    out = _run(workload, seconds=1.0)
    assert out["correct"], out["checks"]
    assert out["checks"]["mismatched_queries"]["value"] == 0
    assert out["checks"]["checked_queries"]["value"] == 4
    assert out["device"]["platform"] == "cpu"
    assert set(out["metrics"]) == {"queries_per_s", "query_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_is_not_correct(workload):
    got = control.control_mismatches(CELLS, workload, SEED)
    assert got["checked_queries"] == 4
    assert got["mismatched_queries"] > 0


def _unchanged_state(monkeypatch):
    from repro.core import bayesnet, mrf

    monkeypatch.setattr(bayesnet, "gibbs_sweep",
                        lambda cbn, vals, *a, **k: vals)
    monkeypatch.setattr(mrf, "half_step", lambda m, labels, *a, **k: labels)


def _half_the_batch(monkeypatch):
    from repro.runtime import batcher

    run = batcher._execute_bucket

    def half(program, key, queries, n_real, n_pad, return_state):
        kept = queries[:max(1, len(queries) // 2)]
        out = run(program, key, kept, len(kept),
                  batcher.pad_size(len(kept)), return_state)
        return [dataclasses.replace(out[i % len(out)], qid=q.qid,
                                    model=q.model)
                for i, q in enumerate(queries)]

    monkeypatch.setattr(batcher, "_execute_bucket", half)


def _altered_answer(monkeypatch):
    from repro.core import bayesnet, mrf

    def altered(draw):
        def wrapped(logp, *a, **k):
            labels = draw(logp, *a, **k)
            first = (0,) * labels.ndim
            return labels.at[first].set((labels[first] + 1) % logp.shape[-1])
        return wrapped

    monkeypatch.setattr(bayesnet, "draw_from_logits",
                        altered(bayesnet.draw_from_logits))
    monkeypatch.setattr(mrf, "draw_from_logits", altered(mrf.draw_from_logits))


@pytest.mark.parametrize("fault", [_unchanged_state, _half_the_batch,
                                   _altered_answer])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_fault_makes_the_run_not_correct(workload, fault, monkeypatch,
                                         fresh_jit):
    fault(monkeypatch)
    out = _run(workload)
    assert not out["correct"]
    assert out["checks"]["mismatched_queries"]["value"] > 0


# -- the energy a configuration states ----------------------------------


def _tiny_mrf_config() -> dict:
    return json.loads((CELLS / "bench/configs/tiny-mrf.json").read_text())


def test_a_field_the_program_lacks_fails_set_up(tmp_path):
    """The entry's keys go to the program's `GridMRF` by name: one it does
    not have fails in set-up, with the program's error naming it, before
    any compile."""
    shutil.copytree(CELLS, tmp_path, dirs_exist_ok=True)
    config = _tiny_mrf_config()
    config["models"]["grid"]["pairwise"] = "truncated_quadratic"
    (tmp_path / "bench/configs/tiny-mrf.json").write_text(json.dumps(config))
    compiles = harness.CompileLog()
    t0 = time.perf_counter()
    try:
        with pytest.raises(TypeError, match="pairwise"):
            harness.run_cell(tmp_path, "tiny.mrf", SEED, 0.3, False, t0,
                             require_tpu=False, log=lambda s: None)
    finally:
        compiles.close()
    assert time.perf_counter() - t0 < 10
    assert compiles.compiles(t0, math.inf) == 0


@pytest.mark.parametrize("key, value", [("pairwise", "truncated_quadratic"),
                                        ("data_cost", "truncated_linear")])
def test_reference_refuses_what_it_does_not_implement(key, value):
    from bench.reference.mrf_gibbs import MRFReference
    from bench.reference.sampler import DrawSpec

    config = _tiny_mrf_config()
    entry = dict(config["models"]["grid"], **{key: value})
    with pytest.raises(ValueError, match=key):
        MRFReference(entry, DrawSpec.from_config(config))
