"""The GAT cell (`pubmed-gat-full`) and the four-chip stream cell
(`imdb-poisson-x4`): their files, the GAT reference and work, the graph
generator at PubMed's counts, and the GAT readers on a recorded trace."""
import json

import numpy as np
import pytest

from chipbench_testlib import ROOT

DATA = ROOT / "tests" / "chipbench" / "data"
NEW_CELLS = ("pubmed-gat-full", "imdb-poisson-x4")
GAT_READERS = ("gat_agg_roofline.gat", "mfu.gat", "idle_share.gat")


def gat_config():
    return json.loads((ROOT / "chipbench" / "configs" / "gat-pubmed.json")
                      .read_text())


@pytest.fixture(scope="module")
def pubmed():
    """The whole PubMed graph of the configuration (about 4 s to build)."""
    from chipbench.bench import load_part

    kind = load_part("kinds", "full_graph_pa")
    return kind.whole_graph(gat_config()["dataset"])


@pytest.mark.parametrize("cell", NEW_CELLS)
def test_new_cells_resolve_their_files_by_name(cell):
    from chipbench.bench import load_cell, load_part, load_reader

    c = load_cell(cell)
    assert callable(load_part("loops", c.traffic["loop"]).drive)
    kind = load_part("kinds", c.traffic["requests"]["kind"])
    assert callable(kind.prepare) and callable(kind.draw)
    model = load_part("models", c.config["model"]["kind"])
    assert callable(model.outputs) and callable(model.layer_work)
    names = [m["name"] for m in c.end_to_end + c.per_layer]
    assert all(callable(load_reader(n)) for n in names)
    if cell == "pubmed-gat-full":
        assert set(GAT_READERS) <= set(names)
        assert {"graphs_per_s", "latency_p95_ms", "setup_s"} <= set(names)
        assert c.chips == 1 and c.traffic["check"]["sample"] == 0
    else:
        assert "latency_p50_ms" in names and c.chips == 4
        assert not set(GAT_READERS) & set(names)


def test_citation_pa_reaches_pubmed(pubmed):
    """Exactly PubMed's 44,338 undirected edges, and the recorded hub: 171
    neighbours (172 nonzeros of A + I in its row)."""
    assert pubmed.n == 19717 and len(pubmed.src) == 88676
    assert pubmed.nnz == 88676 + 19717
    assert pubmed.max_degree == 172


def test_citation_pa_is_citation_where_citation_reaches():
    from chipbench import graphs as G
    from chipbench.kinds.full_graph_pa import citation_pa

    a = G.citation(np.random.default_rng(131), 3327, 9464)
    b = citation_pa(np.random.default_rng(131), 3327, 9464)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


def test_engine_heads_match_the_config():
    """The harness builds engines from ``kind`` and ``dims`` alone: the
    engine's default heads are the configuration's."""
    import jax

    from repro.runtime import InferenceEngine

    model = gat_config()["model"]
    params = InferenceEngine([tuple(d) for d in model["layers"]],
                             kind=model["kind"]).init(jax.random.PRNGKey(0))
    last = len(params) - 1
    for i, (p, h, (fi, fo)) in enumerate(zip(params, model["heads"],
                                             model["layers"])):
        fh = fo if i == last else fo // h
        assert p["a_self"].shape == p["a_nbr"].shape == (h, fh)
        assert p["w"].shape == (fi, h * fh) and p["b"].shape == (fo,)


@pytest.mark.parametrize("readout", [None, "mean"])
def test_reference_agrees_with_the_program(readout):
    """models/gat.py (float32, "highest") against the program's jnp path
    on the CPU, a few graphs served in one block-diagonal batch: the same
    float32 arithmetic in another order, so 1e-5 of the largest output."""
    import jax

    from chipbench import graphs as G
    from chipbench.bench import load_part
    from chipbench.reference import max_gap
    from repro.graphs import from_edges
    from repro.runtime import InferenceEngine, Request

    gat = load_part("models", "gat")
    rng = np.random.default_rng(4)
    dims = [(20, 16), (16, 3)]
    edges = [G.EdgeGraph(*G.citation(rng, n, 2 * (n + n // 3)))
             for n in (200, 37, 41)]
    xs = [rng.standard_normal((e.n, 20)).astype(np.float32) for e in edges]
    eng = InferenceEngine(dims, kind="gat", readout=readout)
    params = eng.init(jax.random.PRNGKey(1))
    res = eng.submit([Request(graph=from_edges(e.n, e.src, e.dst), x=x,
                              rid=i) for i, (e, x) in enumerate(zip(edges, xs))])
    refs = gat.outputs(edges, xs, params, readout=readout)
    assert max_gap([r.output for r in res], refs) < 1e-5
    ctl = gat.outputs(edges, xs, params, readout=readout,
                      dtype=jax.numpy.float8_e4m3fn)
    assert max_gap(ctl, refs) > 1e-2


def test_layer_work_hand_worked():
    from chipbench.bench import load_part

    gat = load_part("models", "gat")
    # V=10, 30 nonzeros, 4 -> 2 heads of 3 concatenated (width 6)
    w = gat.layer_work(10, 30, 4, 6, 2, True)
    att = 6 * 30 * 2 + 2 * 30 * 6
    assert att == gat.attention_work(10, 30, 6, 2).ops == 720
    assert w.ops == 2 * 10 * 4 * 6 + 4 * 10 * 6 + att == 1440
    assert w.bytes == 4 * (10 * 4 + 4 * 6 + 2 * 6 + 10 * 6) + 8 * 30 == 784
    # the same heads averaged to 3 outputs: width 6, plus the mean
    m = gat.layer_work(10, 30, 4, 3, 2, False)
    assert m.ops == w.ops + 10 * 6
    assert m.bytes == 4 * (10 * 4 + 4 * 6 + 2 * 6 + 10 * 3) + 8 * 30
    a = gat.attention_work(10, 30, 6, 2)
    assert a.bytes == 4 * 10 * (6 + 4) + 4 * 10 * 6 + 8 * 30 == 880


def _ctx(pubmed, trace, n_served):
    """A traced pubmed-gat-full window of ``n_served`` answered requests."""
    from types import SimpleNamespace

    from chipbench.bench import (Context, Item, Record, Window, load_cell,
                                 load_part)
    from repro.graphs import BucketPolicy, from_edges

    cell = load_cell("pubmed-gat-full")
    csr = from_edges(pubmed.n, pubmed.src, pubmed.dst)
    ok = SimpleNamespace(status="ok")
    recs = [Record(due=0.0, sent=float(i), done=float(i) + 0.5, result=ok,
                   item=Item(pubmed, csr)) for i in range(n_served)]
    win = Window(records=recs, t0=0.0, t_end=100.0, t_close=100.0,
                 seconds=100.0, n_batches=n_served, compiles=0)
    policy = BucketPolicy(max_graphs=4)
    return Context(cell=cell, setup_s=1.0, win=win,
                   dims=[tuple(d) for d in cell.config["model"]["layers"]],
                   pallas={policy.bucket_of(csr): [True, True]},
                   policy=policy, device_kind="TPU v5 lite",
                   model=load_part("models", "gat"), trace=trace)


def test_readers_on_a_recorded_chip_trace(pubmed):
    """2 s of a traced pubmed-gat-full run on a TPU v5e (12 requests in
    it): each reader gives a share between 0 and 100."""
    from chipbench.bench import load_reader
    from chipbench.work import least_time, peaks

    tr = json.loads((DATA / "trace_pubmed_gat_v5e.json").read_text())
    tr.pop("expected")
    ctx = _ctx(pubmed, tr, 12)
    got = {n: load_reader(n)(ctx) for n in GAT_READERS}
    assert all(0 < v <= 100 for v in got.values()), got
    gat, peak = ctx.model, peaks("TPU v5 lite")
    least = 12 * sum(least_time(gat.attention_work(pubmed.n, pubmed.nnz,
                                                   width, 8), peak)[0]
                     for width in (64, 24))
    from chipbench import trace as T

    assert got["gat_agg_roofline.gat"] == pytest.approx(
        100 * least / (T.kernel_ns(tr, ("gat_agg",)) / 1e9), rel=1e-12)
    assert got["idle_share.gat"] == pytest.approx(100 * T.idle_share(tr))


def test_a_share_over_100_is_refused(pubmed):
    """A kernel time shorter than the least time is a counting fault."""
    from chipbench.bench import load_reader

    tr = json.loads((DATA / "trace_pubmed_gat_v5e.json").read_text())
    tr.pop("expected")
    for evs in tr["devices"].values():
        for e in evs:
            if e[0].startswith("gat_agg"):
                e[2] = 1.0  # 1 ns a call
    with pytest.raises(ValueError, match="above 100"):
        load_reader("gat_agg_roofline.gat")(_ctx(pubmed, tr, 12))


def test_readers_find_nothing_without_a_trace(pubmed):
    from chipbench.bench import load_reader

    ctx = _ctx(pubmed, None, 3)
    assert all(load_reader(n)(ctx) is None for n in GAT_READERS)
