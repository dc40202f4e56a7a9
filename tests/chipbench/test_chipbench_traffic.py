"""The traffic generators: seeds, rates, sizes."""
import numpy as np
import pytest

import chipbench_testlib  # noqa: F401  (puts the repository root on sys.path)
from chipbench import graphs as G
from chipbench.bench import Workload, load_cell, load_part

poisson_offsets = load_part("loops", "poisson").poisson_offsets


def _from_edges(n, src, dst):
    from repro.graphs import from_edges

    return from_edges(n, src, dst)


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_poisson_offsets_same_seed_same_stream(seed):
    a = poisson_offsets(50.0, 4.0, seed)
    assert np.array_equal(a, poisson_offsets(50.0, 4.0, seed))
    assert not np.array_equal(a, poisson_offsets(50.0, 4.0, seed + 1))


def test_poisson_mean_rate_and_window():
    rate, seconds = 400.0, 30.0
    due = poisson_offsets(rate, seconds, 5)
    assert len(due) == rate * seconds
    assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < seconds
    gaps = np.diff(due)
    # exponential gaps: mean 1/rate, coefficient of variation about 1
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.01)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, rel=0.05)


def test_every_seed_sends_the_same_gaps_in_another_order():
    a, b = (np.sort(np.diff(np.append(poisson_offsets(80.0, 5.0, s), 5.0)))
            for s in (1, 2))
    assert np.allclose(a, b)


def test_fresh_graphs_same_seed_same_graphs_and_sizes_fixed():
    ds = load_cell("imdb-poisson").config["dataset"]
    a = G.fresh_graphs(ds, 300, np.random.default_rng([3, 1]))
    b = G.fresh_graphs(ds, 300, np.random.default_rng([3, 1]))
    c = G.fresh_graphs(ds, 300, np.random.default_rng([4, 1]))
    assert all(x.n == y.n and np.array_equal(x.src, y.src)
               for x, y in zip(a, b))
    assert sorted(g.n for g in a) == sorted(g.n for g in c)
    assert [g.n for g in a] != [g.n for g in c]
    mean_n = np.mean([g.n for g in a])
    assert mean_n == pytest.approx(ds["avg_nodes"], rel=0.03)


def test_ego_nets_reach_the_published_edge_count():
    """IMDB-BINARY's 96.53 undirected edges a graph on average, the ego
    linked to every node, so each bucket's ELL width is the node bucket's."""
    ds = load_cell("imdb-poisson").config["dataset"]
    gs = G.fresh_graphs(ds, 2000, np.random.default_rng([7, 1]))
    undirected = [(g.nnz - g.n) / 2 for g in gs]
    assert np.mean(undirected) == pytest.approx(ds["avg_edges"], rel=0.01)
    for g in gs[:200]:
        assert len(g.src) == g.nnz - g.n  # no duplicate edge
        assert g.max_degree == g.n  # the ego: every other node and itself


def test_log_uniform_sizes_within_range():
    s = G.log_uniform_sizes(512, 100, 1024, np.random.default_rng(0))
    assert s.min() >= 100 and s.max() <= 1024
    assert np.median(s) == pytest.approx(np.sqrt(100 * 1024), rel=0.05)


def test_subgraph_pool_sizes_as_declared():
    cell = load_cell("citeseer-sub-poisson")
    req = cell.traffic["requests"]
    wl = Workload(cell, _from_edges)
    _, pool = wl.state
    sizes = [sub.n for sub, _, _ in pool]
    assert len(sizes) == req["pool"]
    assert min(sizes) >= req["min_nodes"] and max(sizes) <= req["max_nodes"]
    d1, d2 = wl.draw(11, 2.0), wl.draw(11, 2.0)
    assert [it.nodes.tolist() for it in d1.items] == \
        [it.nodes.tolist() for it in d2.items]
    assert np.array_equal(d1.tables[0], d2.tables[0])
    assert len(d1.items) == round(cell.traffic["rate_per_s"] * 2.0)
    # another seed: the same subgraphs, as often, in another order
    d3 = wl.draw(12, 51.0)
    d4 = wl.draw(13, 51.0)
    assert sorted(it.edge.n for it in d3.items) == \
        sorted(it.edge.n for it in d4.items)
    assert [it.edge.n for it in d3.items] != [it.edge.n for it in d4.items]


def test_citeseer_graph_matches_the_published_sizes():
    """3327 nodes, 4732 undirected edges (Kipf Table 1; 9464 directed),
    and a hub of 99 neighbours as in citeseer: 4096 rows x ELL 128."""
    from repro.graphs import BucketPolicy

    g = G.whole_graph(load_cell("citeseer-full").config["dataset"])
    assert g.n == 3327 and len(g.src) == 9464
    assert g.nnz == 9464 + 3327 and g.max_degree == 100
    csr = _from_edges(g.n, g.src, g.dst)
    assert BucketPolicy().bucket_of(csr) == (4096, 128)


def test_full_graph_draw_cycles_feature_tables_evenly():
    cell = load_cell("citeseer-full")
    wl = Workload(cell, _from_edges)
    d = wl.draw(9, 1.0)
    k = cell.traffic["requests"]["feature_tables"]
    first = [it.table for it in d.items[:4 * k]]
    assert sorted(first) == sorted(list(range(k)) * 4)
    assert all(t.shape == (3327, 3703) and t.dtype == np.float32
               for t in d.tables)


def test_bfs_and_induced_subgraph():
    # a path 0-1-2-3-4 plus a chord 1-3
    src = np.array([0, 1, 1, 2, 2, 3, 3, 4, 1, 3])
    dst = np.array([1, 0, 2, 1, 3, 2, 4, 3, 3, 1])
    g = G.EdgeGraph(5, src, dst)
    nodes = G.bfs_nodes(g, 0, 3)
    assert nodes.tolist() == [0, 1, 2]
    sub = G.induced(g, np.array([1, 2, 3]))
    assert sub.n == 3 and sorted(zip(sub.src.tolist(), sub.dst.tolist())) == \
        [(0, 1), (0, 2), (1, 0), (1, 2), (2, 0), (2, 1)]
