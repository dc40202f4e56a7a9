"""Graph generators and graph surgery of the benchmark's own.

The two generators follow the program's ``_citation`` and ``_ego``
(``repro/graphs/datasets.py``) but live here, so that a later change to
the program's dataset code cannot move the yardstick, and they reach the
published edge counts, which the program's do not.  Everything here works on plain edge lists (``src``,
``dst`` arrays of directed edges, both directions listed); the program is
handed the graphs through its own ``repro.graphs.from_edges``, and the
reference (:mod:`chipbench.reference`) builds its adjacency from the same
edge lists.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class EdgeGraph:
    """``n`` nodes and directed edges ``src -> dst`` (no self-loops
    needed; duplicates allowed)."""

    n: int
    src: np.ndarray
    dst: np.ndarray

    def unique_edges(self) -> tuple[np.ndarray, np.ndarray]:
        """Deduplicated (src, dst) pairs with a self-loop on every node:
        the nonzeros of A + I."""
        loops = np.arange(self.n, dtype=np.int64)
        src = np.concatenate([np.asarray(self.src, np.int64), loops])
        dst = np.concatenate([np.asarray(self.dst, np.int64), loops])
        keys = np.unique(src * self.n + dst)
        return keys // self.n, keys % self.n

    @property
    def nnz(self) -> int:
        """Nonzeros of A + I (real entries, no padding)."""
        return int(len(self.unique_edges()[0]))

    @property
    def max_degree(self) -> int:
        src, _ = self.unique_edges()
        return int(np.bincount(src, minlength=self.n).max())


def ego(rng: np.random.Generator, n: int, m: int) -> tuple:
    """IMDB-style ego-net of ``n`` nodes and ``m`` undirected edges: node 0
    (the ego) is linked to every other node, the others split into 1-3
    cliques (the ego's films), and edges among the others are then dropped
    or added at random until there are exactly ``m`` (held within the star
    and the complete graph)."""
    n = max(n, 3)
    m = min(max(m, n - 1), n * (n - 1) // 2)
    k = int(rng.integers(1, 4))
    cuts = (np.sort(rng.choice(np.arange(1, n - 1), size=min(k - 1, n - 2),
                               replace=False)) if k > 1 else np.array([], int))
    member = np.zeros(n, np.int64)
    for c in cuts:
        member[c + 1:] += 1
    s, d = np.triu_indices(n, 1)
    star = s == 0
    rest = ~star
    clique = rest & (member[s] == member[d])
    want = m - (n - 1)
    inside, outside = np.flatnonzero(clique), np.flatnonzero(rest & ~clique)
    if len(inside) >= want:
        keep = rng.choice(inside, size=want, replace=False)
    else:
        keep = np.concatenate([inside, rng.choice(outside, size=want - len(inside),
                                                  replace=False)])
    pick = np.concatenate([np.flatnonzero(star), keep])
    src, dst = s[pick], d[pick]
    return n, np.concatenate([src, dst]), np.concatenate([dst, src])


def citation(rng: np.random.Generator, n: int, m: int) -> tuple:
    """Preferential attachment with ``m`` directed edges (``m / 2``
    undirected): each new node links to one or, for a seeded share of the
    nodes, two earlier nodes drawn by degree, so the graph has exactly the
    published edge count and a power-law hub tail."""
    links = np.ones(n, np.int64)
    links[0] = 0
    twice = m // 2 - (n - 1)
    links[rng.choice(np.arange(2, n), size=twice, replace=False)] += 1
    src_l, dst_l = [], []
    deg = np.ones(n, dtype=np.float64)
    order = rng.permutation(n)
    for i in range(1, n):
        p = deg[order[:i]] / deg[order[:i]].sum()
        targets = rng.choice(order[:i], size=links[i], replace=False, p=p)
        for t in targets:
            src_l.append(order[i])
            dst_l.append(t)
            deg[t] += 1
            deg[order[i]] += 1
    src = np.array(src_l)
    dst = np.array(dst_l)
    return n, np.concatenate([src, dst]), np.concatenate([dst, src])


GENERATORS = {"citation": citation, "ego": ego}


def whole_graph(dataset: dict) -> EdgeGraph:
    """The one graph of a node-classification dataset, from the config's
    fixed ``graph_seed`` (the dataset is the same in every run)."""
    rng = np.random.default_rng(int(dataset["graph_seed"]))
    n, src, dst = GENERATORS[dataset["generator"]](
        rng, int(dataset["n_nodes"]), int(dataset["n_edges"])
    )
    return EdgeGraph(n, src, dst)


def stratified_normal_sizes(count: int, mean: float, rel_sd: float,
                            rng: np.random.Generator) -> np.ndarray:
    """``count`` node counts with the quantiles of N(mean, rel_sd * mean),
    floored at 3 and shuffled by ``rng``: every seed draws the same sizes
    in another order."""
    nd = NormalDist(mean, rel_sd * mean)
    q = (np.arange(count) + 0.5) / count
    sizes = np.array([max(3, int(round(nd.inv_cdf(float(p))))) for p in q])
    return rng.permutation(sizes)


def fresh_graphs(dataset: dict, count: int,
                 rng: np.random.Generator) -> list[EdgeGraph]:
    """``count`` graph-classification graphs at the dataset's average size:
    node counts as :func:`stratified_normal_sizes` (the program's
    ``make_graph`` draws N(avg, avg/4)), edges scaled with the node count,
    structure from ``rng``."""
    gen = GENERATORS[dataset["generator"]]
    avg_n, avg_e = float(dataset["avg_nodes"]), float(dataset["avg_edges"])
    out = []
    for n in stratified_normal_sizes(count, avg_n, 0.25, rng):
        m = max(2, int(round(avg_e * n / avg_n)))
        n2, src, dst = gen(rng, int(n), m)
        out.append(EdgeGraph(n2, src, dst))
    return out


def log_uniform_sizes(count: int, lo: int, hi: int,
                      rng: np.random.Generator) -> np.ndarray:
    """``count`` sizes at the quantiles of log-uniform [lo, hi], shuffled."""
    q = (np.arange(count) + 0.5) / count
    sizes = np.rint(np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo))))
    return rng.permutation(sizes.astype(np.int64))


def bfs_nodes(g: EdgeGraph, root: int, size: int) -> np.ndarray:
    """Up to ``size`` nodes reached breadth-first from ``root``, sorted."""
    order = np.argsort(g.src, kind="stable")
    src, dst = np.asarray(g.src)[order], np.asarray(g.dst)[order]
    ptr = np.searchsorted(src, np.arange(g.n + 1))
    seen = {int(root)}
    frontier = [int(root)]
    while frontier and len(seen) < size:
        nxt = []
        for v in frontier:
            for u in dst[ptr[v]:ptr[v + 1]]:
                u = int(u)
                if u not in seen and len(seen) < size:
                    seen.add(u)
                    nxt.append(u)
        frontier = nxt
    return np.array(sorted(seen), dtype=np.int64)


def induced(g: EdgeGraph, nodes: np.ndarray) -> EdgeGraph:
    """The subgraph on ``nodes``, renumbered 0..len(nodes)-1."""
    pos = np.full(g.n, -1, dtype=np.int64)
    pos[nodes] = np.arange(len(nodes))
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    keep = (pos[src] >= 0) & (pos[dst] >= 0) & (src != dst)
    return EdgeGraph(len(nodes), pos[src[keep]], pos[dst[keep]])
