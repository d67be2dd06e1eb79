"""Truncated-quadratic smoothness, masked observations and label counts
past the old 128-lane cap on the grid-MRF datapath, in interpret mode on a
ragged 13 x 10 grid (a height that is neither a multiple of 8 nor easy to
divide, so the fused kernel pads its last tile) with 130 labels: the fused
kernel, its oracle, the eager engine, the served routes and the
benchmark's plain reference agree bit for bit."""

import json
import math
import pathlib
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ky as ky_core
from repro.core import mrf as mrf_mod
from repro.core.graphs import GridMRF
from repro.core.interp import build_exp_weight_lut
from repro.kernels import mrf_tiles, ref
from repro.kernels.mrf_gibbs import kernel_precision, mrf_half_step_kernel
from repro.runtime.batcher import Query
from repro.runtime.engine import Engine, EngineConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]
H, W, V = 13, 10, 130
ENERGY = dict(theta=0.5, h=0.04, data_cost="quadratic",
              pairwise="truncated_quadratic", truncation=200.0)


def _mrf(**kw) -> GridMRF:
    return GridMRF(H, W, V, name="inpaint", **{**ENERGY, **kw})


def _image(seed: int) -> np.ndarray:
    """Seeded gray levels with about a fifth of the pixels unobserved."""
    rng = np.random.default_rng(seed)
    img = rng.integers(0, V, (H, W))
    return np.where(rng.random((H, W)) < 0.2, -1, img).astype(np.int32)


@pytest.mark.parametrize("block_h", [8, None])
def test_fused_kernel_matches_oracle(block_h):
    """Two tiles of 8 rows (three padded) or the chosen single tile: the
    kernel equals `kernels/ref.py` on the same words, both parities."""
    rng = np.random.default_rng(0)
    labels = jnp.asarray(rng.integers(0, V, (H, W)), jnp.int32)
    ev = jnp.asarray(_image(1))
    tab, spec = build_exp_weight_lut()
    precision = kernel_precision(V)
    words = ky_core.random_words(jax.random.key(2), (H, W),
                                 -(-precision * 8 // 32))
    for parity in (0, 1):
        want = ref.mrf_gibbs_half_step(
            labels, ev, words, parity=parity, n_labels=V, exp_table=tab,
            exp_spec=spec, precision=precision, **ENERGY)
        got = mrf_half_step_kernel(
            labels, ev, words.reshape(H, -1),
            tab.reshape(1, -1).astype(jnp.float32), parity=parity,
            n_labels=V, spec=spec, precision=precision, block_h=block_h,
            interpret=True, **ENERGY)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert (np.asarray(want) != np.asarray(labels)).any()


def test_oracle_is_the_eager_half_step():
    """`kernels/ref.py`, fed the words `draw_from_logits` derives from a
    key, makes the eager engine's half-step."""
    rng = np.random.default_rng(3)
    labels = jnp.asarray(rng.integers(0, V, (1, H, W)), jnp.int32)
    ev = jnp.asarray(_image(4))
    tab, spec = build_exp_weight_lut()
    key = jax.random.key(5)
    precision = kernel_precision(V)
    words = ky_core.random_words(key, (H * W,), -(-precision * 8 // 32))
    want = mrf_mod.half_step(_mrf(), labels, ev, key, 1)
    got = ref.mrf_gibbs_half_step(
        labels[0], ev, words, parity=1, n_labels=V, exp_table=tab,
        exp_spec=spec, precision=precision, **ENERGY)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want[0]))


def test_unobserved_pixels_carry_no_data_term():
    mrf = _mrf()
    rng = np.random.default_rng(6)
    labels = jnp.asarray(rng.integers(0, V, (2, H, W)), jnp.int32)
    ev = _image(7)
    hole = ev < 0
    logp = np.asarray(mrf_mod.site_log_potentials(mrf, labels,
                                                  jnp.asarray(ev)))
    smooth = np.asarray(mrf_mod.site_log_potentials(
        _mrf(h=0.0), labels, jnp.asarray(ev)))
    np.testing.assert_array_equal(logp[:, hole], smooth[:, hole])
    assert (logp[:, ~hole] != smooth[:, ~hole]).any()
    # a hole filled with the chain's own labels adds nothing either
    filled = jnp.asarray(np.where(hole, np.asarray(labels[0]), ev))
    np.testing.assert_array_equal(
        np.asarray(mrf_mod.total_energy(mrf, labels[:1], jnp.asarray(ev))),
        np.asarray(mrf_mod.total_energy(mrf, labels[:1], filled)))


def test_truncation_bounds_each_neighbor_term():
    """A neighbor 100 levels away costs theta * truncation, not 10,000."""
    mrf = _mrf(theta=1.0)
    labels = jnp.zeros((1, H, W), jnp.int32).at[0, 5, 5].set(100)
    ev = jnp.full((H, W), -1, jnp.int32)
    logp = np.asarray(mrf_mod.site_log_potentials(mrf, labels, ev))
    # site (4, 5) sees 100 below and 0 elsewhere: value 0 pays 200 once
    assert logp[0, 4, 5, 0] == -200.0
    assert logp[0, 4, 5, 100] == -3 * 200.0


def _serve(fused: bool, slice_iters, n_iters: int = 4) -> dict:
    engine = Engine({"inpaint": _mrf()}, EngineConfig(
        fused=fused, max_batch=4, slice_iters=slice_iters))
    engine.submit([Query(qid=i, model="inpaint", image=_image(10 + i),
                         n_chains=2, n_iters=n_iters, seed=2**31 + 977 + i)
                   for i in range(3)])
    results = engine.run()
    return {q: np.asarray(r.final_state) for q, r in results.items()}


@pytest.fixture(scope="module")
def bench_answers() -> dict:
    """What `bench/reference/mrf_inpaint.py` (nothing of `src/`) draws for
    the served queries."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.reference.mrf_inpaint import MRFReference
    from bench.reference.sampler import DrawSpec

    entry = {"height": H, "width": W, "labels": V, **ENERGY}
    reference = MRFReference(entry, DrawSpec())
    return {q: reference.run(_image(10 + q), 2**31 + 977 + q, n_chains=2,
                             n_iters=4) for q in range(3)}


@pytest.mark.parametrize("fused, slice_iters", [
    (False, None), (True, None), (True, 2)], ids=["unfused", "fused",
                                                  "fused-sliced"])
def test_served_route_equals_the_bench_reference(fused, slice_iters,
                                                 bench_answers):
    """Fused and unfused, sliced and whole: every route serves each query
    the benchmark reference's answer, so the routes agree bit for bit."""
    served = _serve(fused, slice_iters)
    assert served.keys() == bench_answers.keys()
    for q, labels in served.items():
        np.testing.assert_array_equal(labels, bench_answers[q])


def test_tile_rows_pad_ragged_heights_instead_of_one_row_tiles():
    # 179 is prime: where the largest divisor gave 1-row tiles, 24-row
    # tiles pad it to 192 rows (8-row tiles would pad to 184)
    assert mrf_tiles.tile_rows(179, 122, 256) == 24
    assert mrf_tiles.tile_rows(13, 10, 130) == 16
    # no padding where it would cost more than 1/16 of the rows
    assert mrf_tiles.tile_rows(240, 320, 12) == 24
    assert mrf_tiles.tile_rows(64, 64, 4) == 32
    for h, w, v in ((179, 122, 256), (240, 320, 12), (7, 5, 3),
                    (64, 1024, 512)):
        bh = mrf_tiles.tile_rows(h, w, v)
        assert bh % 8 == 0 and bh <= mrf_tiles.MAX_ROWS
        assert mrf_tiles.tile_plane_bytes(bh, w, v) <= max(
            mrf_tiles.TILE_PLANE_BYTES, mrf_tiles.tile_plane_bytes(8, w, v))


def test_a_truncated_quadratic_needs_its_truncation():
    with pytest.raises(TypeError, match="needs a truncation"):
        GridMRF(H, W, V, pairwise="truncated_quadratic")
    with pytest.raises(TypeError, match="takes no truncation"):
        GridMRF(H, W, V, truncation=4.0)
    with pytest.raises(ValueError, match="pairwise"):
        GridMRF(H, W, V, pairwise="truncated_linear", truncation=4.0)


def test_a_field_gridmrf_lacks_fails_bench_set_up(tmp_path):
    """A benchmark MRF entry's energy keys reach `GridMRF` by name, so a key
    the program has no field for fails the cell's set-up with the
    dataclass's own `TypeError`, before any compile."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import harness

    cells = ROOT / "tests/bench/data/cells"
    shutil.copytree(cells, tmp_path, dirs_exist_ok=True)
    path = tmp_path / "bench/configs/tiny-mrf.json"
    config = json.loads(path.read_text())
    config["models"]["grid"]["smoothness"] = "truncated_linear"
    path.write_text(json.dumps(config))
    compiles = harness.CompileLog()
    t0 = time.perf_counter()
    try:
        with pytest.raises(TypeError,
                           match="unexpected keyword argument 'smoothness'"):
            harness.run_cell(tmp_path, "tiny.mrf", 2**31 + 977, 0.3, False,
                             t0, require_tpu=False, log=lambda s: None)
    finally:
        compiles.close()
    assert time.perf_counter() - t0 < 10
    assert compiles.compiles(t0, math.inf) == 0
