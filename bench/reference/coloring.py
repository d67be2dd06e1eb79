"""The chromatic schedule the reference sweeps in: DSATUR colors of the
conflict graph, one round per color in color order, nodes ascending.

DSATUR as published (Brelaz 1979): repeatedly color the uncolored vertex
of highest saturation (distinct neighbor colors), ties to the higher
degree, then to the lower index, with the least color no neighbor holds.
The serving pipeline's small-color merge changes nothing on DSATUR output
(every class above 0 has a neighbor in each lower class), so the rounds
here are the served rounds.
"""

from __future__ import annotations

import heapq

import numpy as np


def dsatur(adj: list[set[int]]) -> np.ndarray:
    n = len(adj)
    colors = np.full(n, -1, np.int64)
    saturation: list[set[int]] = [set() for _ in range(n)]
    degree = [len(a) for a in adj]
    heap = [(0, -degree[v], v) for v in range(n)]
    heapq.heapify(heap)
    for _ in range(n):
        while True:
            sat, _, v = heapq.heappop(heap)
            if colors[v] == -1 and -sat == len(saturation[v]):
                break
        taken = {int(colors[u]) for u in adj[v] if colors[u] != -1}
        c = 0
        while c in taken:
            c += 1
        colors[v] = c
        for u in adj[v]:
            if colors[u] == -1 and c not in saturation[u]:
                saturation[u].add(c)
                heapq.heappush(heap, (-len(saturation[u]), -degree[u], u))
    for v in range(n):
        if any(colors[u] == colors[v] for u in adj[v]):
            raise AssertionError(f"improper coloring at node {v}")
    return colors


def rounds(adj: list[set[int]]) -> list[list[int]]:
    colors = dsatur(adj)
    return [
        [int(v) for v in np.flatnonzero(colors == c)]
        for c in range(int(colors.max()) + 1)
    ]


def moral_adjacency(parents: list[list[int]]) -> list[set[int]]:
    """i ~ j iff j is in the Markov blanket of i."""
    n = len(parents)
    adj: list[set[int]] = [set() for _ in range(n)]
    for child, ps in enumerate(parents):
        family = list(ps) + [child]
        for a in family:
            for b in family:
                if a != b:
                    adj[a].add(b)
    return adj


def grid_adjacency(height: int, width: int) -> list[set[int]]:
    adj: list[set[int]] = [set() for _ in range(height * width)]
    for r in range(height):
        for c in range(width):
            for r2, c2 in ((r + 1, c), (r, c + 1)):
                if r2 < height and c2 < width:
                    adj[r * width + c].add(r2 * width + c2)
                    adj[r2 * width + c2].add(r * width + c)
    return adj
