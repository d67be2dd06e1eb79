"""What decides `correct`: the served answers of a sample of the window's
queries against the plain reference (`bench/reference`), bit for bit.

BN: the final chain states must be equal, and the served marginals must be
the reference's value histogram over the kept sweeps divided by their
count (chains x kept sweeps): the histogram read back from a marginal must
be equal, and the marginal within float32 rounding of it.  MRF: the final
labels must be equal.  A query differing in any of these is a mismatch;
the limit is 0, since an exact comparison has no tolerance to set.
"""

from __future__ import annotations

import dataclasses
import importlib

import numpy as np

from bench import generator
from bench.reference.sampler import DrawSpec

# |served marginal - histogram / count| allowed: float32 rounding of a
# quotient in [0, 1] is below 6e-8; anything larger is a wrong marginal.
MARGINAL_ROUNDING = 1e-6


@dataclasses.dataclass
class Answer:
    """What a query produced: final states, and BN marginals."""

    final_state: np.ndarray
    marginals: np.ndarray | None = None


class Reference:
    """The configuration's reference, one model instance per model name."""

    def __init__(self, config: dict, traffic: dict, plain: dict,
                 weight_bits: int | None = None):
        self.config, self.traffic, self.plain = config, traffic, plain
        self.spec = DrawSpec.from_config(config, weight_bits)
        self.module = importlib.import_module(
            f"bench.reference.{config['reference']}")
        self._models = {}

    def _model(self, name: str):
        if name not in self._models:
            m = self.plain[name]
            if self.config["kind"] == "bn":
                observed = generator.observed_nodes(self.traffic, name, m)
                self._models[name] = self.module.BNReference(
                    m["cards"], m["parents"], m["cpts"], observed, self.spec)
            else:
                self._models[name] = self.module.MRFReference(
                    m["height"], m["width"], m["labels"], m["theta"], m["h"],
                    self.spec)
        return self._models[name]

    def answer(self, q: generator.QuerySpec) -> Answer:
        ref = self._model(q.model)
        if self.config["kind"] == "bn":
            hist, vals = ref.run(q.evidence, q.seed, n_chains=q.n_chains,
                                 n_iters=q.n_iters, burn_in=q.burn_in,
                                 thin=q.thin)
            count = q.n_chains * kept_sweeps(q.n_iters, q.burn_in, q.thin)
            return Answer(vals, (hist / np.float32(max(count, 1))).astype(
                np.float32))
        return Answer(ref.run(q.image, q.seed, n_chains=q.n_chains,
                              n_iters=q.n_iters))


def kept_sweeps(n_iters: int, burn_in: int, thin: int) -> int:
    return sum(1 for t in range(n_iters)
               if t >= burn_in and (t - burn_in) % thin == 0)


def agrees(q: generator.QuerySpec, served: Answer, ref: Answer) -> bool:
    if (served.final_state.shape != ref.final_state.shape
            or not np.array_equal(served.final_state, ref.final_state)):
        return False
    if ref.marginals is None:
        return True
    if served.marginals is None or served.marginals.shape != ref.marginals.shape:
        return False
    count = q.n_chains * kept_sweeps(q.n_iters, q.burn_in, q.thin)
    hist = np.rint(ref.marginals.astype(np.float64) * count)
    served_hist = np.rint(served.marginals.astype(np.float64) * count)
    gap = np.abs(served.marginals.astype(np.float64) - hist / count).max()
    return bool(np.array_equal(served_hist, hist)
                and gap <= MARGINAL_ROUNDING)


def mismatches(reference: Reference, sample) -> int:
    """Queries of [(QuerySpec, Answer)] whose answer is not the reference's."""
    return sum(not agrees(q, a, reference.answer(q)) for q, a in sample)
