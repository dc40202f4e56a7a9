"""Each request is the whole graph of a node-classification dataset, with
features from ``feature_tables`` seeded tables made in set-up, each table
used equally often in a seeded order."""
import numpy as np

from chipbench import graphs as G
from chipbench.bench import Item, stream_rng


def prepare(dataset: dict, req: dict, from_edges):
    base = G.whole_graph(dataset)
    return base, from_edges(base.n, base.src, base.dst)


def draw(state, dataset: dict, req: dict, seed: int, n: int):
    base, csr = state
    f_in, n_tab = int(dataset["n_features"]), int(req["feature_tables"])
    frng = stream_rng(seed, 2)
    tables = [frng.standard_normal((base.n, f_in), np.float32)
              for _ in range(n_tab)]
    grng = stream_rng(seed, 1)
    order = np.concatenate([grng.permutation(n_tab)
                            for _ in range(-(-n // n_tab))])[:n]
    return [Item(base, csr, table=int(k), key=int(k)) for k in order], tables
