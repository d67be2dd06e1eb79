"""Checkerboard (2-color) chromatic Gibbs for grid MRFs (paper Eqn. 7, Fig. 1f).

The regular-PM counterpart of `bayesnet.py`: a 4-connected grid (Potts or
truncated-quadratic smoothness, `GridMRF`) needs exactly two colors, so one Gibbs iteration is two dense half-steps, each
updating every other site simultaneously — AIA's best-case workload (Penguin/
Art image tasks).  The per-site pipeline is the same C2->C1 chain:

    neighbor labels (C4 exchange) -> energy -> LUT-exp weights -> KY draw

`labels` carries a leading chains axis (B, H, W): chains are the DP axis.
`distributed.py` shards (H) across devices and swaps `jnp.roll` for
`lax.ppermute` halo exchange — the neighbor-RF access made ICI-native.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

# see bayesnet.py: chain-state donation is deliberately partial; the
# unusable-leaf warning is expected noise
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)

from repro.core.draws import draw_from_logits
from repro.core.graphs import GridMRF
from repro.core.interp import build_exp_weight_lut
from repro.diag import accum as diag_accum


def neighbor_labels(labels: jax.Array) -> tuple[jax.Array, ...]:
    """(..., H, W) labels -> the (up, down, left, right) neighbor labels of
    every site, -1 past the grid's edge (the free boundary)."""

    def shift(d, axis):
        pad = [(0, 0)] * labels.ndim
        pad[axis] = (1, 0) if d > 0 else (0, 1)
        sl = [slice(None)] * labels.ndim
        sl[axis] = slice(0, -1) if d > 0 else slice(1, None)
        return jnp.pad(labels[tuple(sl)], pad, constant_values=-1)

    h_ax, w_ax = labels.ndim - 2, labels.ndim - 1
    return shift(1, h_ax), shift(-1, h_ax), shift(1, w_ax), shift(-1, w_ax)


def log_potentials(
    nbrs, evidence, v, *, theta: float, h: float, data_cost: str = "potts",
    pairwise: str = "potts", truncation: float | None = None,
) -> jax.Array:
    """log P(site = v | neighbors, evidence) in `GridMRF`'s float32
    operation order, on arrays that broadcast: `nbrs` the (up, down, left,
    right) neighbor labels (-1 where there is none), `evidence` the
    observation (< 0 where unobserved), `v` the candidate values.  The one
    spelling of the energy: the eager engine, the kernel oracle and the
    fused kernel's tiles all call it."""
    if pairwise == "potts":
        n = [(q == v).astype(jnp.float32) for q in nbrs]
        smooth = theta * (((n[0] + n[1]) + n[2]) + n[3])
    elif pairwise == "truncated_quadratic":
        n = []
        for q in nbrs:
            d = (v - q).astype(jnp.float32)
            n.append(jnp.where(q >= 0, jnp.minimum(d * d, truncation), 0.0))
        smooth = -theta * (((n[0] + n[1]) + n[2]) + n[3])
    else:
        raise ValueError(f"pairwise {pairwise!r}")
    if data_cost == "potts":
        data = h * (evidence == v).astype(jnp.float32)
    elif data_cost == "quadratic":
        diff = (evidence - v).astype(jnp.float32)
        data = -h * diff * diff
    else:
        raise ValueError(f"data_cost {data_cost!r}")
    return smooth + jnp.where(evidence >= 0, data, 0.0)


def energy_fields(mrf: GridMRF) -> dict:
    """The `log_potentials` keywords of a model."""
    return dict(theta=mrf.theta, h=mrf.h, data_cost=mrf.data_cost,
                pairwise=mrf.pairwise, truncation=mrf.truncation)


def site_log_potentials(
    mrf: GridMRF, labels: jax.Array, evidence: jax.Array
) -> jax.Array:
    """Unnormalized log P(site = v | neighbors, evidence) for every site/value.
    labels (..., H, W), evidence (H, W) -> (..., H, W, V)."""
    v_range = jnp.arange(mrf.n_labels, dtype=labels.dtype)
    return log_potentials(
        [q[..., None] for q in neighbor_labels(labels)],
        evidence[..., None], v_range, **energy_fields(mrf),
    )


def checkerboard_mask(h: int, w: int, parity: int) -> jax.Array:
    ii = jnp.arange(h)[:, None] + jnp.arange(w)[None, :]
    return (ii % 2) == parity


@functools.partial(
    jax.jit, static_argnames=("mrf", "parity", "sampler", "exp_spec")
)
def half_step(
    mrf: GridMRF,
    labels: jax.Array,
    evidence: jax.Array,
    key: jax.Array,
    parity: int,
    sampler: str = "lut_ky",
    exp_table=None,
    exp_spec=None,
    pin_mask: jax.Array | None = None,
) -> jax.Array:
    """Update all sites of one checkerboard color simultaneously (Alg. 2).

    `pin_mask` ((H, W) bool) excludes pinned pixels from the update: draws
    are still computed for the whole grid (the random words per site do not
    depend on the mask, keeping pinned and unpinned runs comparable bit for
    bit on the free sites of the first half-step), but pinned sites keep
    their current labels."""
    if exp_table is None:
        exp_table, exp_spec = build_exp_weight_lut()
    logp = site_log_potentials(mrf, labels, evidence)
    new = draw_from_logits(logp, key, sampler, exp_table, exp_spec)
    mask = checkerboard_mask(mrf.height, mrf.width, parity)
    if pin_mask is not None:
        mask = mask & ~pin_mask
    return jnp.where(mask, new, labels)


@dataclasses.dataclass
class MRFChainState:
    """Resume point for a grid-MRF Gibbs run: carrying (labels, key) across
    `mrf_gibbs_loop` calls makes a sliced run bit-identical to an
    uninterrupted one (the key is split once per iteration in sequence and
    there is no burn-in/thinning state to realign).

    `quality` optionally carries a `diag.accum.QualityAccum` over the
    flattened site axis; None stays an empty pytree subtree so existing
    jit caches and carried states are untouched when diagnostics are off."""

    labels: jax.Array  # (B, H, W) int32 current chain states
    key: jax.Array  # PRNG key as of the next iteration
    quality: object = None  # diag.accum.QualityAccum | None


jax.tree_util.register_dataclass(
    MRFChainState, ["labels", "key", "quality"], []
)


def init_labels(
    mrf: GridMRF,
    key: jax.Array,
    n_chains: int,
    pin_mask: jax.Array | None = None,
    pin_vals: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Random (B, H, W) label init with pinned pixels clamped to their
    values; the random tensor covers every site regardless of the mask (same
    reasoning as `bayesnet.init_chain_values`).  Returns (labels, key)."""
    k0, key = jax.random.split(key)
    labels = jax.random.randint(
        k0, (n_chains, mrf.height, mrf.width), 0, mrf.n_labels, jnp.int32
    )
    if pin_mask is not None:
        labels = jnp.where(pin_mask[None], pin_vals[None], labels)
    return labels, key


def mrf_gibbs_loop(
    mrf: GridMRF,
    evidence: jax.Array,
    key: jax.Array | None,
    n_chains: int,
    n_iters: int,
    sampler: str,
    pin_mask: jax.Array | None = None,
    pin_vals: jax.Array | None = None,
    carry: MRFChainState | None = None,
    return_state: bool = False,
    diag_total=None,
    diag_batch: int = diag_accum.DEFAULT_BATCH_LEN,
):
    """The eager iteration body shared by `run_mrf_gibbs` and the batched
    serving path (which vmaps it over queries): n_iters x (even half-step,
    odd half-step), pins held fixed throughout.

    `carry` resumes a previous call's `MRFChainState` (then `key` is ignored
    and may be None) and `n_iters` counts *additional* iterations — sliced
    runs are bit-exact with uninterrupted ones.  `return_state=True` returns
    (labels, state) instead of labels alone.

    `diag_total` (the query's total iteration budget) switches the
    streaming quality accumulator on for a fresh run: every iteration's
    post-sweep labels feed a per-site one-hot into `diag.accum.update`
    (MRF runs have no burn-in/thinning, so every iteration is kept).  The
    update consumes no randomness — the label stream is bit-identical with
    diagnostics on.  On a resumed carry the accumulator rides in with the
    state and `diag_total` is ignored."""
    exp_table, exp_spec = build_exp_weight_lut()
    if carry is None:
        labels, key = init_labels(mrf, key, n_chains, pin_mask, pin_vals)
        quality = None
        if diag_total is not None:
            quality = diag_accum.make_accum(
                n_chains, mrf.height * mrf.width, mrf.n_labels,
                jnp.asarray(diag_total, jnp.int32), diag_batch,
            )
    else:
        labels, key, quality = carry.labels, carry.key, carry.quality

    def body(t, carry):
        labels, key, quality = carry
        key, ka, kb = jax.random.split(key, 3)
        labels = half_step(
            mrf, labels, evidence, ka, 0, sampler, exp_table, exp_spec,
            pin_mask,
        )
        labels = half_step(
            mrf, labels, evidence, kb, 1, sampler, exp_table, exp_spec,
            pin_mask,
        )
        if quality is not None:
            onehot = (
                labels.reshape(labels.shape[0], -1)[..., None]
                == jnp.arange(mrf.n_labels, dtype=labels.dtype)
            ).astype(jnp.int32)
            quality = diag_accum.update(
                quality, onehot, jnp.asarray(True)
            )
        return labels, key, quality

    labels, key, quality = jax.lax.fori_loop(
        0, n_iters, body, (labels, key, quality)
    )
    if return_state:
        return labels, MRFChainState(labels=labels, key=key, quality=quality)
    return labels


@functools.partial(
    jax.jit,
    static_argnames=("mrf", "n_chains", "n_iters", "sampler", "return_state"),
    # sliced serving: resume in place instead of copying the carried labels
    # every slice (a passed carry is consumed — see bayesnet.run_gibbs)
    donate_argnames=("carry",),
)
def run_mrf_gibbs(
    mrf: GridMRF,
    evidence: jax.Array,
    key: jax.Array | None,
    n_chains: int = 1,
    n_iters: int = 30,
    sampler: str = "lut_ky",
    pin_mask: jax.Array | None = None,
    pin_vals: jax.Array | None = None,
    carry: MRFChainState | None = None,
    return_state: bool = False,
    diag_total=None,
    diag_batch: int = diag_accum.DEFAULT_BATCH_LEN,
):
    """Full chromatic Gibbs: n_iters x (even half-step, odd half-step).

    Returns final labels (B, H, W) — the approximate MPE state for the
    denoising benchmarks (paper Eqn. 4).  `pin_mask`/`pin_vals` ((H, W)
    bool / int32) clamp pixels at known labels for the whole run.
    `carry`/`return_state` slice the run: see `mrf_gibbs_loop`
    (`diag_total`/`diag_batch` switch its quality accumulator on)."""
    return mrf_gibbs_loop(
        mrf, evidence, key, n_chains, n_iters, sampler, pin_mask, pin_vals,
        carry=carry, return_state=return_state,
        diag_total=diag_total, diag_batch=diag_batch,
    )


def total_energy(mrf: GridMRF, labels: jax.Array, evidence: jax.Array):
    """log of the unnormalized joint (paper Eqn. 3/7 numerator): the pair
    terms once per grid edge plus the data terms of the observed pixels —
    a test/convergence metric."""
    pairs = ((labels[..., :, 1:], labels[..., :, :-1]),
             (labels[..., 1:, :], labels[..., :-1, :]))
    if mrf.pairwise == "potts":
        smooth = mrf.theta * sum(
            (a == b).astype(jnp.float32).sum((-1, -2)) for a, b in pairs)
    else:
        d = [(a - b).astype(jnp.float32) for a, b in pairs]
        smooth = -mrf.theta * sum(
            jnp.minimum(x * x, mrf.truncation).sum((-1, -2)) for x in d)
    observed = evidence >= 0
    if mrf.data_cost == "potts":
        data = mrf.h * ((labels == evidence) & observed).astype(
            jnp.float32).sum((-1, -2))
    else:
        diff = (labels - evidence).astype(jnp.float32)
        data = -mrf.h * jnp.where(observed, diff * diff, 0.0).sum((-1, -2))
    return smooth + data


def make_denoising_problem(
    h: int, w: int, n_labels: int, noise: float, seed: int = 0
):
    """Synthetic Penguin/Art-style task: piecewise-constant image + label noise.
    Returns (clean (H,W), noisy evidence (H,W))."""
    rng = np.random.default_rng(seed)
    clean = np.zeros((h, w), np.int32)
    for _ in range(max(3, n_labels)):
        r0, c0 = rng.integers(0, h), rng.integers(0, w)
        rh, cw = rng.integers(h // 4, h), rng.integers(w // 4, w)
        clean[r0 : r0 + rh, c0 : c0 + cw] = rng.integers(0, n_labels)
    flip = rng.random((h, w)) < noise
    noisy = np.where(flip, rng.integers(0, n_labels, (h, w)), clean)
    return clean, noisy.astype(np.int32)
