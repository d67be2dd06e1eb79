"""Models of a configuration, generated from its file alone.

BN: structure-matched replicas of bnlearn repository networks.  Each entry
gives the published node count, arc count and largest in-degree, which
the replica has exactly; which nodes the arcs join, the arities and the
CPT rows (Dirichlet(0.8) draws) are drawn from the entry's seed, since the
real networks are not available offline (an assumption every
configuration file names).  MRF: the entry itself, the energy the
configuration states (`bench/spec.py`); observations are drawn by
`bench/images/`.  The generators are fixed copies, so a change to the
program's own generators cannot move the benchmark.
"""

from __future__ import annotations

import numpy as np


def random_cpt(rng: np.random.Generator, shape: tuple[int, ...]) -> np.ndarray:
    """Dirichlet(0.8) rows, floored at 1e-4 and renormalized."""
    flat = rng.dirichlet(np.full(shape[-1], 0.8), size=int(np.prod(shape[:-1])))
    flat = np.clip(flat, 1e-4, None).reshape(shape)
    return flat / flat.sum(-1, keepdims=True)


def random_bayesnet(n_nodes: int, n_arcs: int, max_in_degree: int, arities,
                    seed: int) -> dict:
    """Nodes in topological order, `n_arcs` arcs and largest in-degree
    `max_in_degree`: one node that can have that many parents gets them,
    the other arcs fall uniformly on the remaining parent slots (node i has
    min(i, max_in_degree) of them), and each node's parents are drawn among
    0..i-1.  Arities are drawn uniformly from `arities`."""
    rng = np.random.default_rng(seed)
    cards = rng.choice(list(arities), size=n_nodes)
    cap = [min(i, max_in_degree) for i in range(n_nodes)]
    widest = [i for i in range(n_nodes) if cap[i] == max_in_degree]
    slots = sum(cap) - max_in_degree
    if not widest or not max_in_degree <= n_arcs <= max_in_degree + slots:
        raise ValueError(f"no DAG on {n_nodes} nodes has {n_arcs} arcs and "
                         f"largest in-degree {max_in_degree}")
    k = [0] * n_nodes
    k[int(rng.choice(widest))] = max_in_degree
    owners = [i for i in range(n_nodes) for _ in range(cap[i] - k[i])]
    for s in rng.choice(len(owners), size=n_arcs - max_in_degree,
                        replace=False):
        k[owners[s]] += 1
    parents: list[list[int]] = [
        sorted(rng.choice(i, size=k[i], replace=False).tolist()) if k[i]
        else [] for i in range(n_nodes)]
    cpts = [
        random_cpt(rng, tuple(int(cards[p]) for p in ps) + (int(cards[i]),))
        for i, ps in enumerate(parents)
    ]
    return {"cards": np.asarray(cards, np.int64), "parents": parents,
            "cpts": cpts}


# Keys of an MRF entry that are not fields of the program's `GridMRF`.
MRF_SHAPE = ("height", "width", "labels", "image")


def mrf_fields(entry: dict) -> dict:
    """The energy's fields of an MRF entry, by the program's names."""
    return {k: v for k, v in entry.items() if k not in MRF_SHAPE}


def build(config: dict) -> dict[str, dict]:
    """{model name: plain model} for a configuration file's `models`."""
    out = {}
    for name, m in config["models"].items():
        if config["kind"] == "bn":
            out[name] = random_bayesnet(
                m["n_nodes"], m["n_arcs"], m["max_in_degree"], m["arities"],
                m["seed"])
        else:
            out[name] = dict(m)
    return out


def n_free(config: dict, model: dict, n_observed: int) -> int:
    """Variables a query resamples each sweep."""
    if config["kind"] == "bn":
        return len(model["cards"]) - n_observed
    return model["height"] * model["width"] - n_observed
