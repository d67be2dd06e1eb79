"""Streaming sampling-quality accumulators — the "are the answers right"
half of observability (PR 6's tracer is the "where does time go" half).

One `QualityAccum` pytree rides inside the Gibbs iteration loops
(`bayesnet.gibbs_run_loop`, `mrf.mrf_gibbs_loop`, and the schedule
backend's round cores) and ingests the same per-sweep one-hot tensor the
marginal histogram already computes — a pure-jax Welford update, no host
sync, no randomness consumed, so enabling diagnostics never changes a
draw stream.  The accumulator lives in the chain-state carry
(`BNChainState.quality` / `MRFChainState.quality`), which makes it
carry-over safe: a run sliced at any boundaries accumulates bit-identical
statistics to an uninterrupted one, because the kept-draw index is derived
from the accumulator's own counters, never from where a slice started.

What it tracks, per chain, per node, per value of the one-hot marginal
indicator x = 1[X_node = v]:

  * split-chain mean/variance (Welford, two halves at `split_at` — the
    kept-index midpoint of the query's *total* budget, fixed at
    accumulator creation so every slice agrees where the split falls);
    `summarize` folds the 2B sub-chains into Gelman-Rubin split R-hat.
  * batch-means autocorrelation state (`batch_len`-draw batches, Welford
    over batch means) -> effective sample size per chain,
    ESS = kept * Var(x) / (L * Var(batch means)), summed over chains.
  * the pooled mean itself is the streaming marginal estimate `p_hat`
    (cross-checked against the histogram-based marginals in tests).

`summarize` runs on the host (numpy) at the end of a run — the jit side
only ever carries the raw moments.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

# batch length for the batch-means ESS estimator: long enough to absorb
# the few-sweep autocorrelation of chromatic Gibbs on the bench nets,
# short enough that quick CI budgets still complete >= 2 batches
DEFAULT_BATCH_LEN = 8

# kept*chains headroom before the int32 histogram in BNChainState.hist
# (and the float32 Welford counts) start losing exactness
_INT32_HEADROOM = 2**30


@dataclasses.dataclass
class QualityAccum:
    """Raw streaming moments; every field is pytree *data* (no statics), so
    one jitted program serves every budget/split/batch-length setting."""

    counts: jax.Array  # (2,) int32 kept draws per split half
    mean: jax.Array  # (2, B, S, V) f32 Welford mean per half/chain/site/value
    m2: jax.Array  # (2, B, S, V) f32 Welford sum of squared deviations
    split_at: jax.Array  # () int32 kept index where half 1 begins
    batch_len: jax.Array  # () int32 batch-means batch length
    bm_count: jax.Array  # () int32 completed batches
    bm_mean: jax.Array  # (B, S, V) f32 Welford mean over batch means
    bm_m2: jax.Array  # (B, S, V) f32 Welford m2 over batch means
    cur_sum: jax.Array  # (B, S, V) f32 running sum of the open batch
    cur_n: jax.Array  # () int32 kept draws in the open batch


jax.tree_util.register_dataclass(
    QualityAccum,
    ["counts", "mean", "m2", "split_at", "batch_len", "bm_count",
     "bm_mean", "bm_m2", "cur_sum", "cur_n"],
    [],
)


def make_accum(
    n_chains: int,
    n_sites: int,
    n_values: int,
    total_kept,
    batch_len: int = DEFAULT_BATCH_LEN,
) -> QualityAccum:
    """Fresh accumulator for a run that will keep `total_kept` draws in
    total (the *whole* query budget, not the current slice — the split
    point must be the same wherever the run is sliced).  `total_kept` may
    be a traced scalar: it enters as data, so per-lane totals vmap."""
    shape2 = (2, n_chains, n_sites, n_values)
    shape1 = (n_chains, n_sites, n_values)
    total_kept = jnp.asarray(total_kept, jnp.int32)
    return QualityAccum(
        counts=jnp.zeros(2, jnp.int32),
        mean=jnp.zeros(shape2, jnp.float32),
        m2=jnp.zeros(shape2, jnp.float32),
        split_at=jnp.maximum(total_kept // 2, 1),
        batch_len=jnp.asarray(batch_len, jnp.int32),
        bm_count=jnp.zeros((), jnp.int32),
        bm_mean=jnp.zeros(shape1, jnp.float32),
        bm_m2=jnp.zeros(shape1, jnp.float32),
        cur_sum=jnp.zeros(shape1, jnp.float32),
        cur_n=jnp.zeros((), jnp.int32),
    )


def kept_count(n_iters, burn_in: int, thin: int):
    """Kept draws of a fresh run: |{t in [0, n_iters) : t >= burn_in and
    (t - burn_in) % thin == 0}| — the loop's own keep gate, counted."""
    n_iters = jnp.asarray(n_iters, jnp.int32)
    return jnp.maximum((n_iters - burn_in + thin - 1) // thin, 0)


def update(q: QualityAccum, onehot: jax.Array, keep) -> QualityAccum:
    """Fold one sweep's one-hot indicators ((B, S, V), any numeric dtype)
    into the accumulator.  `keep` is the loop's burn-in/thinning gate; a
    masked-out sweep leaves every statistic bit-identical (computed with
    `where`, never with control flow, so the update traces once)."""
    x = onehot.astype(jnp.float32)
    keep = jnp.asarray(keep, bool)
    kept_idx = q.counts[0] + q.counts[1]
    half = (kept_idx >= q.split_at).astype(jnp.int32)
    sel = (jnp.arange(2, dtype=jnp.int32) == half) & keep  # (2,)
    counts = q.counts + sel.astype(jnp.int32)
    selb = sel[:, None, None, None]
    denom = jnp.maximum(counts, 1).astype(jnp.float32)[:, None, None, None]
    delta = x[None] - q.mean
    mean_new = q.mean + delta / denom
    m2_new = q.m2 + delta * (x[None] - mean_new)
    mean = jnp.where(selb, mean_new, q.mean)
    m2 = jnp.where(selb, m2_new, q.m2)
    # batch-means: accumulate the open batch; fold its mean into the
    # batch-level Welford stats when it fills
    cur_sum = jnp.where(keep, q.cur_sum + x, q.cur_sum)
    cur_n = q.cur_n + keep.astype(jnp.int32)
    fold = keep & (cur_n >= q.batch_len)
    bmean = cur_sum / jnp.maximum(q.batch_len, 1).astype(jnp.float32)
    bm_count = q.bm_count + fold.astype(jnp.int32)
    bdenom = jnp.maximum(bm_count, 1).astype(jnp.float32)
    bdelta = bmean - q.bm_mean
    bm_mean_new = q.bm_mean + bdelta / bdenom
    bm_m2_new = q.bm_m2 + bdelta * (bmean - bm_mean_new)
    bm_mean = jnp.where(fold, bm_mean_new, q.bm_mean)
    bm_m2 = jnp.where(fold, bm_m2_new, q.bm_m2)
    cur_sum = jnp.where(fold, jnp.zeros_like(cur_sum), cur_sum)
    cur_n = jnp.where(fold, 0, cur_n)
    return QualityAccum(
        counts=counts, mean=mean, m2=m2, split_at=q.split_at,
        batch_len=q.batch_len, bm_count=bm_count, bm_mean=bm_mean,
        bm_m2=bm_m2, cur_sum=cur_sum, cur_n=cur_n,
    )


# ---------------------------------------------------------------------------
# host-side summary
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class QualitySnapshot:
    """Host-side reduction of a `QualityAccum`: per-node convergence
    diagnostics plus the scalar roll-ups the serving metrics and the CLI
    thresholds consume.  `rhat`/`ess` are NaN where undefined (a node with
    no varying value — e.g. clamped evidence — has nothing to diagnose);
    `rhat` is +inf where chains are stuck in disjoint modes (zero within-
    chain variance, nonzero between), which is exactly the breach the
    split-initialization test injects."""

    rhat: np.ndarray  # (S,) worst split R-hat over the node's values
    ess: np.ndarray | None  # (S,) total ESS over chains; None if < 2 batches
    p_hat: np.ndarray  # (S, V) pooled streaming marginal estimate
    kept: int
    n_chains: int
    split_at: int
    batch_len: int
    n_batches: int
    rhat_max: float | None
    ess_min: float | None
    overflow_risk: bool
    finite: bool

    def brief(self) -> dict:
        """The scalar row serving metrics carry around."""
        return {
            "rhat_max": self.rhat_max,
            "ess_min": self.ess_min,
            "kept": self.kept,
            "n_chains": self.n_chains,
            "n_batches": self.n_batches,
            "overflow_risk": self.overflow_risk,
            "finite": self.finite,
        }

    def to_dict(self) -> dict:
        d = self.brief()
        d["split_at"] = self.split_at
        d["batch_len"] = self.batch_len
        d["rhat"] = [None if not np.isfinite(r) and not np.isinf(r)
                     else (float(r) if np.isfinite(r) else "inf")
                     for r in self.rhat]
        if self.ess is not None:
            d["ess"] = [None if np.isnan(e) else float(e) for e in self.ess]
        return d


def _combine_welford(na, ma, m2a, nb, mb, m2b):
    """Chan et al. parallel-variance merge of two Welford states."""
    n = na + nb
    safe = np.maximum(n, 1)
    delta = mb - ma
    mean = ma + delta * (nb / safe)
    m2 = m2a + m2b + delta * delta * (na * nb / safe)
    return n, mean, m2


def summarize(
    q: QualityAccum,
    cards=None,
    free_mask=None,
    total_kept: int | None = None,
) -> QualitySnapshot:
    """Reduce raw moments to the quality snapshot (host numpy).

    `cards` ((S,) value cardinalities) masks padded value slots out of the
    diagnostics; `free_mask` ((S,) bool) restricts the rhat_max / ess_min
    roll-ups to unclamped nodes (clamped nodes are constant and carry NaN
    diagnostics either way, but an explicit mask keeps intent visible).
    `total_kept` (the query's whole budget) flags an accumulator that was
    summarized mid-run — callers that slice pass it so `kept` mismatches
    surface as `finite=False` rather than silently under-counting."""
    counts = np.asarray(q.counts, np.int64)  # (2,)
    mean = np.asarray(q.mean, np.float64)  # (2, B, S, V)
    m2 = np.asarray(q.m2, np.float64)
    _, n_chains, n_sites, n_values = mean.shape
    kept = int(counts.sum())

    value_ok = np.ones((n_sites, n_values), bool)
    if cards is not None:
        cards = np.asarray(cards)
        value_ok = np.arange(n_values)[None, :] < cards[:, None]
    node_ok = np.ones(n_sites, bool)
    if free_mask is not None:
        node_ok = np.asarray(free_mask, bool)

    # ---- split R-hat over the 2B sub-chains -------------------------------
    active = [h for h in (0, 1) if counts[h] >= 2]
    rhat_nv = np.full((n_sites, n_values), np.nan)
    if active:
        n_sub = int(counts[active].min())
        # (M, S, V) sub-chain means and (unbiased) variances
        sub_mean = mean[active].reshape(-1, n_sites, n_values)
        sub_var = (m2[active] / np.maximum(counts[active, None, None, None]
                                           - 1, 1)
                   ).reshape(-1, n_sites, n_values)
        w = sub_var.mean(0)
        b = n_sub * sub_mean.var(0, ddof=1) if sub_mean.shape[0] > 1 else (
            np.zeros_like(w))
        var_plus = (n_sub - 1) / n_sub * w + b / n_sub
        tiny = 1e-12
        varies = (w > tiny) | (b > tiny)
        with np.errstate(divide="ignore", invalid="ignore"):
            r = np.sqrt(var_plus / w)
        # stuck-apart chains: no within variance, real between variance
        r = np.where((w <= tiny) & (b > tiny), np.inf, r)
        rhat_nv = np.where(varies & value_ok, r, np.nan)

    with np.errstate(invalid="ignore"):
        rhat_node = np.full(n_sites, np.nan)
        has = ~np.all(np.isnan(rhat_nv), axis=1)
        rhat_node[has] = np.nanmax(rhat_nv[has], axis=1)

    # ---- batch-means ESS --------------------------------------------------
    bm_count = int(np.asarray(q.bm_count))
    batch_len = int(np.asarray(q.batch_len))
    ess_node = None
    if bm_count >= 2 and kept >= 2:
        var_bm = np.asarray(q.bm_m2, np.float64) / (bm_count - 1)  # (B, S, V)
        # whole-run per-chain variance: merge the two split halves
        _, _, m2c = _combine_welford(
            counts[0], mean[0], m2[0], counts[1], mean[1], m2[1]
        )
        s2 = m2c / max(kept - 1, 1)  # (B, S, V)
        tiny = 1e-12
        with np.errstate(divide="ignore", invalid="ignore"):
            ess = kept * s2 / (batch_len * var_bm)
        ess = np.where(s2 <= tiny, np.nan, np.minimum(ess, kept))
        # anticorrelated-beyond-batch case: zero batch variance with real
        # within variance — every kept draw is effectively independent
        ess = np.where((s2 > tiny) & (var_bm <= tiny), float(kept), ess)
        # sum over chains; a constant (stuck) chain contributes zero
        # effective samples, and the cell is undefined only when *every*
        # chain is constant there
        ess_nv = np.where(np.isnan(ess), 0.0, ess).sum(0)
        ess_nv = np.where(np.isnan(ess).all(0) | ~value_ok, np.nan, ess_nv)
        with np.errstate(invalid="ignore"):
            ess_node = np.full(n_sites, np.nan)
            has = ~np.all(np.isnan(ess_nv), axis=1)
            ess_node[has] = np.nanmin(ess_nv[has], axis=1)

    # ---- pooled marginal estimate -----------------------------------------
    weight = counts[:, None, None, None].astype(np.float64)
    pooled = (mean * weight).sum(0) / max(kept, 1)  # (B, S, V)
    p_hat = np.where(value_ok, pooled.mean(0), 0.0)

    finite = bool(
        np.isfinite(mean).all() and np.isfinite(m2).all()
        and np.isfinite(np.asarray(q.bm_m2)).all()
    )
    if total_kept is not None and kept != int(total_kept):
        finite = False
    overflow_risk = kept * n_chains >= _INT32_HEADROOM

    sel = node_ok & ~np.isnan(rhat_node)
    rhat_max = float(np.max(rhat_node[sel])) if sel.any() else None
    ess_min = None
    if ess_node is not None:
        sel = node_ok & ~np.isnan(ess_node)
        ess_min = float(np.min(ess_node[sel])) if sel.any() else None
    return QualitySnapshot(
        rhat=rhat_node,
        ess=ess_node,
        p_hat=p_hat,
        kept=kept,
        n_chains=n_chains,
        split_at=int(np.asarray(q.split_at)),
        batch_len=batch_len,
        n_batches=bm_count,
        rhat_max=rhat_max,
        ess_min=ess_min,
        overflow_risk=overflow_risk,
        finite=finite,
    )
