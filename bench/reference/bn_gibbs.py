"""Reference chromatic Gibbs on a discrete Bayesian network with runtime
evidence: what each served BN query must equal, bit for bit.

Semantics of one query (seed s, evidence e on a fixed node set):
  * key = PRNG key of s; (k0, key) = split(key); every node starts at
    randint(k0, (chains, n), 0, card), evidence nodes at their value;
  * each sweep: (key, sub) = split(key); one key per round from
    split(sub, rounds); round by round, every free node of the round draws
    from log P(x_i | Markov blanket) = log cpt_i + sum over children, the
    factors added left to right (own CPT first, children ascending), in
    float32; the rounds are DSATUR colors with evidence nodes removed;
  * sweep t (from 0) is counted into the per-node value histogram when
    t >= burn_in and (t - burn_in) % thin == 0, over all chains.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import coloring
from bench.reference.sampler import NEG_INF, DrawSpec, draw


class BNReference:
    """One network and observed-node set; `run` is jitted per budget."""

    def __init__(self, cards, parents, cpts, observed, spec: DrawSpec):
        self.cards = np.asarray(cards, np.int64)
        self.n = len(self.cards)
        self.v = int(self.cards.max())
        self.spec = spec
        observed = set(int(x) for x in observed)
        children = [[] for _ in range(self.n)]
        for c, ps in enumerate(parents):
            for p in ps:
                children[p].append(c)
        offsets, tables, off = [], [], 0
        for cpt in cpts:
            offsets.append(off)
            tables.append(np.log(np.asarray(cpt, np.float64).reshape(-1)))
            off += cpt.size
        self.arena = np.concatenate(tables).astype(np.float32)
        self.rounds = []
        for nodes in coloring.rounds(coloring.moral_adjacency(parents)):
            free = [v for v in nodes if v not in observed]
            if free:
                self.rounds.append(self._round_tables(
                    free, parents, children, offsets))
        self.observed = np.zeros(self.n, bool)
        self.observed[sorted(observed)] = True
        self._run = jax.jit(self._sample, static_argnames=(
            "n_chains", "n_iters", "burn_in", "thin"))

    def _round_tables(self, free, parents, children, offsets):
        factors = [[i] + children[i] for i in free]
        f_max = max(len(f) for f in factors)
        s_max = max(len(parents[f]) + 1 for fs in factors for f in fs)
        shape = (len(free), f_max, s_max)
        off = np.full(shape[:2], -1, np.int64)
        stride = np.zeros(shape, np.int64)
        scope = np.zeros(shape, np.int64)
        is_self = np.zeros(shape, bool)
        for a, (i, fs) in enumerate(zip(free, factors)):
            for b, f in enumerate(fs):
                sc = list(parents[f]) + [f]
                dims = [int(self.cards[x]) for x in sc]
                st = np.cumprod([1] + dims[::-1][:-1])[::-1]
                off[a, b] = offsets[f]
                stride[a, b, :len(sc)] = st
                scope[a, b, :len(sc)] = sc
                is_self[a, b, :len(sc)] = [x == i for x in sc]
        return dict(
            nodes=jnp.asarray(free, jnp.int32),
            cards=jnp.asarray(self.cards[free], jnp.int32),
            off=jnp.asarray(off, jnp.int32),
            stride=jnp.asarray(stride, jnp.int32),
            scope=jnp.asarray(scope, jnp.int32),
            is_self=jnp.asarray(is_self),
        )

    def _log_conditionals(self, r, vals):
        """(chains, n_c, V) float32 log P(x_i = v | blanket) + const."""
        arena = jnp.asarray(self.arena)
        values = jnp.arange(self.v, dtype=jnp.int32)
        held = vals[:, r["scope"]][..., None]  # (B, n_c, F, S, 1)
        x = jnp.where(r["is_self"][None, ..., None], values, held)
        addr = r["off"][None, :, :, None] + jnp.sum(
            r["stride"][None, ..., None] * x, axis=-2)  # (B, n_c, F, V)
        terms = jnp.where(r["off"][None, :, :, None] >= 0,
                          arena[jnp.maximum(addr, 0)], 0.0)
        logp = terms[:, :, 0]
        for f in range(1, terms.shape[2]):
            logp = logp + terms[:, :, f]
        return jnp.where(values < r["cards"][None, :, None], logp, NEG_INF)

    def _sample(self, evidence, seed, *, n_chains, n_iters, burn_in, thin):
        k0, key = jax.random.split(jax.random.key(seed))
        cards = jnp.asarray(self.cards, jnp.int32)
        start = jax.random.randint(
            k0, (n_chains, self.n), 0, jnp.maximum(cards[None], 1), jnp.int32)
        vals = jnp.where(jnp.asarray(self.observed)[None], evidence[None],
                         start)
        values = jnp.arange(self.v, dtype=jnp.int32)

        def sweep(t, state):
            vals, key, hist = state
            key, sub = jax.random.split(key)
            keys = jax.random.split(sub, len(self.rounds))
            for r, k in zip(self.rounds, keys):
                labels = draw(self._log_conditionals(r, vals), k, self.spec)
                vals = vals.at[:, r["nodes"]].set(labels)
            keep = (t >= burn_in) & ((t - burn_in) % thin == 0)
            counts = (vals[..., None] == values).astype(jnp.int32).sum(0)
            return vals, key, hist + jnp.where(keep, counts, 0)

        hist = jnp.zeros((self.n, self.v), jnp.int32)
        vals, _, hist = jax.lax.fori_loop(0, n_iters, sweep, (vals, key, hist))
        return hist, vals

    def run(self, evidence: dict, seed: int, *, n_chains: int, n_iters: int,
            burn_in: int, thin: int) -> tuple[np.ndarray, np.ndarray]:
        """(histogram (n, V), final values (chains, n)) of one query."""
        ev = np.zeros(self.n, np.int32)
        for node, val in evidence.items():
            ev[int(node)] = int(val)
        hist, vals = self._run(
            jnp.asarray(ev), jnp.asarray(seed, jnp.uint32), n_chains=n_chains,
            n_iters=n_iters, burn_in=burn_in, thin=thin)
        return np.asarray(hist), np.asarray(vals)

