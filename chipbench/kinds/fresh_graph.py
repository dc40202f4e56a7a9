"""Each request is a graph-classification graph of its own, made from the
seed at the dataset's average size, with its own seeded features."""
import numpy as np

from chipbench import graphs as G
from chipbench.bench import Item, stream_rng


def prepare(dataset: dict, req: dict, from_edges):
    return from_edges


def draw(from_edges, dataset: dict, req: dict, seed: int, n: int):
    f_in = int(dataset["n_features"])
    frng = stream_rng(seed, 2)
    edges = G.fresh_graphs(dataset, n, stream_rng(seed, 1))
    return [Item(e, from_edges(e.n, e.src, e.dst),
                 x=frng.standard_normal((e.n, f_in), np.float32))
            for e in edges], []
