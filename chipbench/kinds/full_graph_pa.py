"""Each request is the whole graph of a node-classification dataset made
by :func:`citation_pa`, with features from ``feature_tables`` seeded tables
made in set-up, each table used equally often in a seeded order.

``full_graph`` builds its graph with ``graphs.citation``, whose nodes link
to one or two earlier nodes each, so it cannot reach a dataset with more
than two undirected edges a node (PubMed has 2.25).  :func:`citation_pa`
is the same preferential attachment with any number of links a node; on
counts ``citation`` reaches it draws the very same graph."""
import numpy as np

from chipbench import graphs as G
from chipbench.bench import Item
from chipbench.kinds import full_graph


def citation_pa(rng: np.random.Generator, n: int, m: int) -> tuple:
    """Preferential attachment with ``m`` directed edges (``m / 2``
    undirected): each new node links to ``(m / 2) // (n - 1)`` earlier
    nodes (as many as there are, for the first few), and a seeded share of
    the nodes with room to one more, drawn by degree, so the graph has
    exactly the published edge count and a power-law hub tail."""
    und = m // 2
    room = np.arange(n)  # node i can link to its i predecessors
    links = np.minimum(und // (n - 1), room)
    extra = und - int(links.sum())
    links[rng.choice(np.flatnonzero(links < room), size=extra,
                     replace=False)] += 1
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


def whole_graph(dataset: dict) -> G.EdgeGraph:
    """The dataset's one graph, from its fixed ``graph_seed``."""
    rng = np.random.default_rng(int(dataset["graph_seed"]))
    return G.EdgeGraph(*citation_pa(rng, int(dataset["n_nodes"]),
                                    int(dataset["n_edges"])))


def prepare(dataset: dict, req: dict, from_edges):
    base = whole_graph(dataset)
    return base, from_edges(base.n, base.src, base.dst)


draw = full_graph.draw
