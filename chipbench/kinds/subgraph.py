"""Each request is a breadth-first subgraph of the dataset's graph, from a
fixed pool of ``pool`` roots cut in set-up (``pool_seed``), its size drawn
log-uniform in ``min_nodes``..``max_nodes``; features are the matching rows
of one seeded table.  Every seed sends the same entries (each pool entry
``n // pool`` or one more times, the same ones more) in another order."""
import numpy as np

from chipbench import graphs as G
from chipbench.bench import Item, stream_rng


def prepare(dataset: dict, req: dict, from_edges):
    base = G.whole_graph(dataset)
    prng = np.random.default_rng(int(req["pool_seed"]))
    roots = prng.choice(base.n, size=int(req["pool"]), replace=False)
    sizes = G.log_uniform_sizes(int(req["pool"]), int(req["min_nodes"]),
                                int(req["max_nodes"]), prng)
    pool = []
    for r, s in zip(roots, sizes):
        nodes = G.bfs_nodes(base, int(r), int(s))
        sub = G.induced(base, nodes)
        pool.append((sub, from_edges(sub.n, sub.src, sub.dst), nodes))
    return base, pool


def draw(state, dataset: dict, req: dict, seed: int, n: int):
    base, pool = state
    table = stream_rng(seed, 2).standard_normal(
        (base.n, int(dataset["n_features"])), np.float32)
    order = stream_rng(seed, 1).permutation(np.arange(n) % len(pool))
    return [Item(pool[k][0], pool[k][1], table=0, nodes=pool[k][2])
            for k in order], [table]
