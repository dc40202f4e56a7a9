"""Unit tests for block-diagonal batching and the pow2 bucket router
(`repro.graphs.csr.block_diagonal`, `repro.graphs.batching`)."""
import numpy as np
import pytest

from repro.graphs import batching
from repro.graphs import (
    BucketPolicy,
    CSRGraph,
    assemble,
    block_diagonal,
    bucketize,
    from_edges,
    next_pow2,
)


def line_graph(n: int) -> CSRGraph:
    """0-1-2-...-(n-1) path, GCN-normalized with self loops."""
    src = np.arange(n - 1)
    dst = src + 1
    return from_edges(n, np.concatenate([src, dst]), np.concatenate([dst, src]))


def star_graph(n: int) -> CSRGraph:
    """Hub 0 connected to 1..n-1 (hub degree n-1: an 'evil row')."""
    spokes = np.arange(1, n)
    hub = np.zeros(n - 1, dtype=np.int64)
    return from_edges(n, np.concatenate([hub, spokes]),
                      np.concatenate([spokes, hub]))


class TestBlockDiagonal:
    def test_row_ptr_and_col_offsets(self):
        a, b = line_graph(4), star_graph(5)
        batched = block_diagonal([a, b])
        assert batched.n_nodes == a.n_nodes + b.n_nodes
        assert batched.n_edges == a.n_edges + b.n_edges
        # row_ptr: a's pointers, then b's shifted by a's edge count
        np.testing.assert_array_equal(
            batched.row_ptr[: a.n_nodes + 1], a.row_ptr
        )
        np.testing.assert_array_equal(
            batched.row_ptr[a.n_nodes :], b.row_ptr + a.n_edges
        )
        # col_idx: b's columns shifted by a's node count
        np.testing.assert_array_equal(batched.col_idx[: a.n_edges], a.col_idx)
        np.testing.assert_array_equal(
            batched.col_idx[a.n_edges :], b.col_idx + a.n_nodes
        )
        batched.validate()

    def test_values_concatenate_and_stay_normalized(self):
        """Degree normalization is per member graph: batching must not
        re-normalize across graphs."""
        a, b = line_graph(6), star_graph(7)
        batched = block_diagonal([a, b])
        np.testing.assert_array_equal(batched.values[: a.n_edges], a.values)
        np.testing.assert_array_equal(batched.values[a.n_edges :], b.values)
        # and the dense form is literally the block-diagonal of the members
        dense = batched.to_dense()
        np.testing.assert_allclose(dense[: a.n_nodes, : a.n_nodes], a.to_dense())
        np.testing.assert_allclose(dense[a.n_nodes :, a.n_nodes :], b.to_dense())
        assert dense[: a.n_nodes, a.n_nodes :].sum() == 0.0
        assert dense[a.n_nodes :, : a.n_nodes].sum() == 0.0

    def test_degrees_preserved(self):
        graphs = [line_graph(3), star_graph(4), line_graph(5)]
        batched = block_diagonal(graphs)
        np.testing.assert_array_equal(
            batched.nnz, np.concatenate([g.nnz for g in graphs])
        )


class TestBucketPolicy:
    def test_next_pow2(self):
        assert [next_pow2(n) for n in (0, 1, 2, 3, 31, 32, 33, 1000)] == [
            1, 1, 2, 4, 32, 32, 64, 1024,
        ]

    def test_node_bucket_floors_and_rounds(self):
        pol = BucketPolicy(min_nodes=32, min_degree=8)
        assert pol.node_bucket(5) == 32  # floored
        assert pol.node_bucket(32) == 32  # exact boundary stays
        assert pol.node_bucket(33) == 64
        assert pol.degree_bucket(3) == 8
        assert pol.degree_bucket(9) == 16

    def test_bucket_of_uses_max_degree(self):
        pol = BucketPolicy(min_nodes=4, min_degree=2)
        g = star_graph(9)  # hub degree 8 + self loop = 9
        assert pol.bucket_of(g) == (16, 16)

    def test_slot_count(self):
        pol = BucketPolicy(max_graphs=8)
        assert pol.slot_count(1) == 1
        assert pol.slot_count(3) == 4
        assert pol.slot_count(8) == 8
        with pytest.raises(ValueError, match="max_graphs"):
            pol.slot_count(9)

    def test_bucketize_routes_in_arrival_order(self):
        pol = BucketPolicy(min_nodes=4, min_degree=2)
        graphs = [line_graph(4), star_graph(9), line_graph(3), star_graph(10)]
        routed = bucketize(graphs, pol)
        assert routed[pol.bucket_of(graphs[0])] == [0, 2]
        assert routed[pol.bucket_of(graphs[1])] == [1, 3]


class TestAssemble:
    POL = BucketPolicy(min_nodes=8, min_degree=4, max_graphs=8)

    def test_shapes_segments_and_padding(self):
        graphs = [line_graph(5), line_graph(7), line_graph(6)]
        batch = assemble(graphs, self.POL)
        assert (batch.v_bucket, batch.d_bucket) == (8, 4)
        assert batch.v_total == 8 * 4  # 3 graphs round up to 4 slots
        assert batch.n_graphs == 3
        assert batch.n_pad == 32 - 18
        batch.graph.validate()
        # segment ids label member nodes 0..2 in order; pad rows carry 3
        np.testing.assert_array_equal(batch.segment_ids[:5], 0)
        np.testing.assert_array_equal(batch.segment_ids[5:12], 1)
        np.testing.assert_array_equal(batch.segment_ids[12:18], 2)
        np.testing.assert_array_equal(batch.segment_ids[18:], 3)
        # pad rows are isolated zero-weight self loops
        assert batch.graph.values[batch.graph.row_ptr[18] :].sum() == 0.0
        np.testing.assert_array_equal(batch.graph.nnz[18:], 1)

    def test_boundary_graph_fills_its_bucket_exactly(self):
        """A graph landing exactly on the bucket boundary pads by zero."""
        g = line_graph(8)  # node bucket is exactly 8
        batch = assemble([g], self.POL)
        assert batch.v_bucket == 8
        assert batch.v_total == 8
        assert batch.n_pad == 0
        np.testing.assert_array_equal(batch.segment_ids, 0)

    def test_mixed_buckets_rejected(self):
        with pytest.raises(ValueError, match="different buckets"):
            assemble([line_graph(5), line_graph(20)], self.POL)

    def test_features_and_split_round_trip(self):
        graphs = [line_graph(5), line_graph(7)]
        batch = assemble(graphs, self.POL)
        xs = [np.full((5, 3), 1.0, np.float32), np.full((7, 3), 2.0, np.float32)]
        x = batch.batch_features(xs)
        assert x.shape == (batch.v_total, 3)
        assert (x[12:] == 0).all()  # pad rows zeroed
        back = batch.split_nodes(x)
        for orig, got in zip(xs, back):
            np.testing.assert_array_equal(orig, got)

    def test_feature_validation(self):
        batch = assemble([line_graph(5)], self.POL)
        with pytest.raises(ValueError, match="feature arrays"):
            batch.batch_features([])
        with pytest.raises(ValueError, match="rows"):
            batch.batch_features([np.zeros((4, 3), np.float32)])

    def test_empty_batch_rejected(self):
        with pytest.raises(ValueError, match="at least one"):
            assemble([], self.POL)


def reference_batch_features(batch, xs):
    """The formula `GraphBatch.batch_features` replaced: a zeroed block
    and one copy of the concatenated members."""
    out = np.zeros((batch.v_total, xs[0].shape[1]), dtype=np.float32)
    out[: int(batch.sizes.sum())] = np.concatenate(xs, axis=0)
    return out


def member_features(sizes, dtypes, f=5, seed=0):
    rng = np.random.default_rng(seed)
    xs = [rng.standard_normal((n, f)).astype(dt) for n, dt in zip(sizes, dtypes)]
    xs[0][0, :2] = [-0.0, np.nan]  # sign of zero and NaN survive the cast
    return xs


class TestBatchFeatures:
    POL = BucketPolicy(min_nodes=8, min_degree=4, max_graphs=8)

    @pytest.mark.parametrize(
        "sizes, n_pad",
        [((5,), 3), ((8,), 0), ((5, 7, 6), 14), ((8, 8), 0)],
        ids=["one-padded", "one-exact", "several-padded", "several-exact"],
    )
    @pytest.mark.parametrize("dtypes", ["float32", "float64", "mixed"])
    def test_matches_zeroed_concatenate_bit_for_bit(self, sizes, n_pad, dtypes):
        batch = assemble([line_graph(n) for n in sizes], self.POL)
        assert batch.n_pad == n_pad
        kinds = {"mixed": [np.float64, np.float32]}.get(dtypes, [dtypes])
        xs = member_features(sizes, (kinds * len(sizes))[: len(sizes)])
        got = batch.batch_features(xs)
        want = reference_batch_features(batch, xs)
        assert got.dtype == np.float32 and got.shape == want.shape
        np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))

    def test_pad_rows_zeroed_over_uninitialised_memory(self, monkeypatch):
        batch = assemble([line_graph(5), line_graph(7), line_graph(6)], self.POL)
        xs = member_features((5, 7, 6), [np.float32] * 3)
        calls = []

        def nan_empty(shape, dtype=float, **kw):
            calls.append(shape)
            return np.full(shape, np.nan, dtype=dtype, **kw)

        class NanNumpy:
            empty = staticmethod(nan_empty)

            def __getattr__(self, name):
                return getattr(np, name)

        monkeypatch.setattr(batching, "np", NanNumpy())
        got = batch.batch_features(xs)
        assert calls == [(batch.v_total, 5)]
        assert batch.n_pad > 0
        np.testing.assert_array_equal(got[18:].view(np.uint32), 0)
        np.testing.assert_array_equal(
            got.view(np.uint32), reference_batch_features(batch, xs).view(np.uint32)
        )

    @pytest.mark.parametrize("sizes", [(8,), (5, 7, 6)])
    def test_result_aliases_no_input(self, sizes):
        batch = assemble([line_graph(n) for n in sizes], self.POL)
        xs = member_features(sizes, [np.float32] * len(sizes))
        before = [x.copy() for x in xs]
        got = batch.batch_features(xs)
        assert not any(np.shares_memory(got, x) for x in xs)
        got[:] = 7.0
        for x, b in zip(xs, before):
            np.testing.assert_array_equal(x.view(np.uint32), b.view(np.uint32))

    def test_each_call_returns_a_new_block(self):
        batch = assemble([line_graph(5)], self.POL)
        xs = member_features((5,), [np.float32])
        a, b = batch.batch_features(xs), batch.batch_features(xs)
        assert a is not b and not np.shares_memory(a, b)

    @pytest.mark.parametrize("bad", [(7, 4), (7, 6), (7,), (7, 5, 1)])
    def test_member_width_must_match_first(self, bad):
        batch = assemble([line_graph(5), line_graph(7)], self.POL)
        xs = [np.zeros((5, 5), np.float32), np.zeros(bad, np.float32)]
        with pytest.raises(ValueError, match=r"must be \(n, 5\)"):
            batch.batch_features(xs)
