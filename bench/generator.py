"""The one generator every traffic mix goes through.

A mix file (`traffic/<name>.json`) fixes the closed loop: its tenants (a
model and how many clients send to it), the query budget, and how query
contents are drawn.  A round of the loop is one query from every client,
so every round has the same bucket composition; the seed only changes the
contents: evidence values, images and chain seeds.

The observed-node set of each BN model is fixed by the mix's
`pattern_seed` and the model's name, not by the run's seed: each set is
its own executable, so a set drawn from the run's seed would make every
run compile anew.
"""

from __future__ import annotations

import dataclasses
import zlib

import numpy as np

from bench import models as models_mod
from bench import spec

WINDOW, WARMUP = 0, 1  # independent query streams of one seed


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    qid: int
    model: str
    seed: int
    n_chains: int
    n_iters: int
    burn_in: int
    thin: int
    sampler: str
    evidence: dict | None = None  # BN: {node: value}
    image: np.ndarray | None = None  # MRF: (H, W) observation


def clients(traffic: dict) -> list[str]:
    """The model each client sends to, in client order."""
    return [t["model"] for t in traffic["tenants"]
            for _ in range(t["clients"])]


def observed_nodes(traffic: dict, name: str, model: dict) -> np.ndarray:
    """The fixed observed-node set of a BN model under this mix."""
    n = len(model["cards"])
    rng = np.random.default_rng(
        [traffic["pattern_seed"], zlib.crc32(name.encode())])
    k = int(n * traffic["observed_fraction"])
    return np.sort(rng.choice(n, size=k, replace=False))


def site_updates(traffic: dict, config: dict, plain: dict) -> int:
    """Variables resampled by one round: chains x sweeps x free variables,
    summed over clients."""
    q = traffic["query"]
    total = 0
    for name in clients(traffic):
        n_obs = (len(observed_nodes(traffic, name, plain[name]))
                 if config["kind"] == "bn" else 0)
        total += q["n_chains"] * q["n_iters"] * models_mod.n_free(
            config, plain[name], n_obs)
    return total


def observation(draw, model: dict, traffic: dict, seed: int) -> np.ndarray:
    """One observation of an MRF model: int32 (H, W), as the contract of
    `bench/images/` states."""
    image = draw(model["height"], model["width"], model["labels"], traffic,
                 seed)
    shape = (model["height"], model["width"])
    if image.dtype != np.int32 or image.shape != shape:
        raise ValueError(f"an observation must be int32 of shape {shape}; "
                         f"got {image.dtype} {image.shape}")
    return image


class Stream:
    """Rounds of queries drawn from (seed, stream) in order: round k is the
    same whatever number of rounds is drawn."""

    def __init__(self, traffic: dict, config: dict, plain: dict, seed: int,
                 stream: int):
        self.traffic, self.config, self.plain = traffic, config, plain
        self.rng = np.random.default_rng([int(seed), stream])
        self.clients = clients(traffic)
        self.next_qid = stream << 40
        self.observed = {}
        self.images = {}
        for name in dict.fromkeys(self.clients):
            m = plain[name]
            if config["kind"] == "bn":
                self.observed[name] = observed_nodes(traffic, name, m)
            else:
                draw = spec.image_drawer(m.get("image", "label_noise"))
                self.images[name] = [
                    observation(draw, m, traffic,
                                int(self.rng.integers(1 << 16)))
                    for _ in range(traffic["image_pool"])
                ]

    def round(self) -> list[QuerySpec]:
        q = self.traffic["query"]
        out = []
        for name in self.clients:
            evidence = image = None
            if self.config["kind"] == "bn":
                nodes = self.observed[name]
                cards = self.plain[name]["cards"][nodes]
                values = self.rng.integers(0, cards)
                evidence = {int(a): int(b) for a, b in zip(nodes, values)}
            else:
                pool = self.images[name]
                image = pool[int(self.rng.integers(len(pool)))]
            out.append(QuerySpec(
                qid=self.next_qid, model=name,
                seed=int(self.rng.integers(1 << 30)),
                n_chains=q["n_chains"], n_iters=q["n_iters"],
                burn_in=q.get("burn_in", 0), thin=q.get("thin", 1),
                sampler=q["sampler"], evidence=evidence, image=image,
            ))
            self.next_qid += 1
        return out

    def rounds(self, n: int) -> list[list[QuerySpec]]:
        return [self.round() for _ in range(n)]
