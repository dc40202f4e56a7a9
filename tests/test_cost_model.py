"""Hand-verified cases for the per-phase cost model (paper Table 1)."""
import numpy as np
import pytest

from repro.core import (
    AcceleratorConfig,
    GNNLayerWorkload,
    PhaseOrder,
    aggregation_cost,
    combination_cost,
    intra,
    named_dataflow,
    pipelined_elements,
    table3_buffering,
)

HW = AcceleratorConfig(n_pes=512, gb_bandwidth=10**9)  # no bandwidth stalls


class TestCombinationTraffic:
    """GEMM V=G=F=4 with 2x2x2 tiles: trips = 2 per dim."""

    def test_output_stationary(self):
        # {VsGs}Ft — Table 1 row 1: inputs and weights stream every step,
        # partial sums accumulate temporally in the PE.
        df = intra("VsGsFt", "cmb", V=2, G=2)
        c = combination_cost(df, 4, 4, 4, HW)
        assert c.cycles == 2 * 2 * 4  # T_F = 1 -> 4 F-steps
        assert c.gb_reads["inp"] == 2 * 2 * 4 * (2 * 1)  # re-read per G tile
        assert c.gb_reads["wt"] == 2 * 2 * 4 * (1 * 2)
        assert c.gb_writes["out"] == 16  # written once, no psum spills
        assert "psum" not in c.gb_writes

    def test_weight_stationary(self):
        # {GsFs}Vt — Table 1 row 2: weights stay, V streams under them.
        df = intra("GsFsVt", "cmb", G=2, F=2)
        c = combination_cost(df, 4, 4, 4, HW)
        assert c.cycles == 2 * 2 * 4
        # each weight tile fetched exactly once: F*G elements total
        assert c.gb_reads["wt"] == 16
        # reduction loop (F) is above the V loop -> psums spill
        assert c.gb_writes["psum"] > 0
        assert c.gb_writes["out"] == 16

    def test_input_stationary(self):
        # {VsFs}Gt — Table 1 row 3: input tile stays, weights stream.
        df = intra("VsFsGt", "cmb", V=2, F=2)
        c = combination_cost(df, 4, 4, 4, HW)
        assert c.gb_reads["inp"] == 16  # each input tile once
        # weight re-fetched per (V, G) step
        assert c.gb_reads["wt"] == 2 * 2 * 4 * 2

    def test_macs_invariant(self):
        for spec in ["VsGsFt", "GsFsVt", "VsFsGt", "VtGtFt", "FsGsVt"]:
            df = intra(spec, "cmb", V=2, G=2, F=2)
            assert combination_cost(df, 8, 6, 10, HW).macs == 8 * 6 * 10


class TestAggregationCost:
    nnz = np.array([3, 1, 2, 2])

    def test_lockstep_evil_row(self):
        # T_V = 2, temporal N: tile trip counts are the tile max (lockstep)
        df = intra("VsFsNt", "agg", V=2, F=2)
        c = aggregation_cost(df, self.nnz, 4, HW)
        assert c.cycles == 2 * (3 + 2)  # f_trips=2, max nnz per tile 3,2
        assert c.macs == 8 * 4

    def test_spatial_n_compresses_depth(self):
        df = intra("VsFsNs", "agg", V=2, F=2, N=2)
        c = aggregation_cost(df, self.nnz, 4, HW)
        assert c.cycles == 2 * (2 + 1)  # ceil(3/2)+ceil(2/2)

    def test_adjacency_reread_when_f_outside_n(self):
        df = intra("VsFsNt", "agg", V=2, F=2)
        c = aggregation_cost(df, self.nnz, 4, HW)
        assert c.gb_reads["adj"] == 8 * 2  # per F pass
        df2 = intra("VsNtFs", "agg", V=2, F=2)
        c2 = aggregation_cost(df2, self.nnz, 4, HW)
        assert c2.gb_reads["adj"] == 8

    def test_psum_spill_when_n_outside_f(self):
        df = intra("VsNtFs", "agg", V=2, F=2)
        c = aggregation_cost(df, self.nnz, 4, HW)
        assert c.gb_writes["psum"] > 0
        df2 = intra("VsFsNt", "agg", V=2, F=2)
        c2 = aggregation_cost(df2, self.nnz, 4, HW)
        assert "psum" not in c2.gb_writes

    def test_gathered_input_no_reuse(self):
        df = intra("VsFsNt", "agg", V=2, F=2)
        c = aggregation_cost(df, self.nnz, 4, HW)
        assert c.gb_reads["inp"] == 8 * 4  # E x feat

    def test_footprint_guard(self):
        df = intra("VsFsNs", "agg", V=64, F=64, N=4)
        with pytest.raises(ValueError, match="PE budget"):
            aggregation_cost(df, self.nnz, 4, HW)


class TestTable3Buffering:
    wl = GNNLayerWorkload(np.full(64, 4), f_in=32, g_out=8)

    def test_seq_full_intermediate(self):
        df = named_dataflow("Seq-Nt", T_V_AGG=4, T_F_AGG=4)
        assert table3_buffering(df, self.wl) == 64 * 32

    def test_sp_optimized_zero(self):
        df = named_dataflow("EnGN", T_V_AGG=4, T_F_AGG=4, T_V_CMB=4, T_F_CMB=4)
        assert table3_buffering(df, self.wl) == 0

    def test_pp_row_granularity(self):
        # PP row: 2 x T_V_max x F
        df = named_dataflow("HyGCN", T_F_AGG=8, T_V_CMB=4, T_G=8)
        assert df.granularity.value == "row"
        assert table3_buffering(df, self.wl) == 2 * 4 * 32

    def test_pp_element_granularity(self):
        from repro.core import GNNDataflow, InterPhase, intra as mk

        df = GNNDataflow(
            InterPhase.PP,
            PhaseOrder.AC,
            mk("VsFsNt", "agg", V=4, F=8),
            mk("VsFsGt", "cmb", V=4, F=8),
        )
        assert df.granularity.value == "element"
        assert table3_buffering(df, self.wl) == 2 * 4 * 8

    def test_pp_ca_row_granularity_uses_agg_v_tile(self):
        # CA intermediate (X.W) is V x G; the aggregation (second) phase
        # consumes it per *output vertex* tile, so Pel's row term must use
        # agg T_V — not T_N, which indexes gathered neighbor rows.
        from repro.core import GNNDataflow, InterPhase, intra as mk

        df = GNNDataflow(
            InterPhase.PP,
            PhaseOrder.CA,
            mk("NsVtFs", "agg", N=4, F=8),
            mk("VsGsFt", "cmb", V=2, G=4),
        )
        assert df.granularity.value == "row"
        # rows in flight = max(cmb T_V = 2, agg T_V = 1); feat = G = 8
        assert pipelined_elements(df, self.wl) == 2 * self.wl.g_out
        assert table3_buffering(df, self.wl) == 2 * 2 * self.wl.g_out

    def test_pel_max_of_tile_sizes(self):
        # imbalanced tiles: Pel uses the max per dim (paper Sec. 4.4)
        from repro.core import GNNDataflow, InterPhase, intra as mk

        df = GNNDataflow(
            InterPhase.PP,
            PhaseOrder.AC,
            mk("VsFsNt", "agg", V=2, F=8),
            mk("VsFsGt", "cmb", V=4, F=4),
        )
        assert pipelined_elements(df, self.wl) == 4 * 8


class TestAttentionCost:
    """An attention (GAT) layer: its two phases at the computed width H*F'
    plus the score and softmax work, nnz*H."""

    NNZ = np.array([3, 1, 5, 2, 4, 1, 2, 6], np.int64)

    def wl(self, heads, concat=True, g_out=8):
        return GNNLayerWorkload(self.NNZ, 16, g_out, heads=heads,
                                concat=concat)

    def test_width_and_fixed_weight_twin(self):
        assert self.wl(8).width == 8
        assert self.wl(8, concat=False, g_out=3).width == 24
        twin = self.wl(8, concat=False, g_out=3).fixed_weight()
        assert (twin.heads, twin.g_out, twin.f_in) == (0, 24, 16)
        plain = GNNLayerWorkload(self.NNZ, 16, 8)
        assert plain.fixed_weight() is plain and plain.width == 8

    def test_hand_worked_edge_term(self):
        from repro.core.cost_model import ATTN_OPS_PER_EDGE_HEAD, attention_cost

        c = attention_cost(self.wl(4), HW)
        e, v = int(self.NNZ.sum()), len(self.NNZ)
        assert c.macs == 2 * v * 8 + ATTN_OPS_PER_EDGE_HEAD * e * 4
        assert c.cycles == c.macs / HW.n_pes
        assert c.gb_reads["att"] == e * 4
        assert attention_cost(GNNLayerWorkload(self.NNZ, 16, 8), HW).cycles == 0

    @pytest.mark.parametrize("concat", [True, False])
    def test_edge_term_grows_with_heads(self, concat):
        from repro.core import simulate
        from repro.core.cost_model import attention_cost
        from repro.core.schedule import default_dataflow

        g_out = 8 if concat else 2
        costs = [attention_cost(self.wl(h, concat, g_out), HW).cycles
                 for h in (1, 2, 8)]
        assert costs[0] < costs[1] < costs[2]
        # the simulator charges it on top of the same two phases
        df = default_dataflow("seq", "CA", band_size=4)
        fixed = simulate(df, self.wl(8).fixed_weight(), HW)
        one, eight = (simulate(df, self.wl(h), HW) for h in (1, 8))
        assert fixed.cycles < one.cycles < eight.cycles
        # each (edge, head) reads a score, each (node, head) writes one
        assert eight.gb_accesses["att"] == 8 * (self.NNZ.sum() + len(self.NNZ))
