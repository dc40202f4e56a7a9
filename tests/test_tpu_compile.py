"""Compile-only checks: the Pallas aggregation kernels compile for a TPU v5e.

Nothing runs here.  The chip is described (``jax.experimental.topologies``)
and each kernel wrapper is lowered and compiled for one of its devices at
the widths the serving path uses: the citeseer bucket of the paper's
2-layer GCN (4096 rows, ELL width 64, 3703 -> 16 -> 6), the whole-citeseer
bucket whose hub sets ELL width 128, an IMDB-BINARY bucket (64 slots of 32
nodes, ELL width 32, 136 -> 16 -> 2), a batched mutag bucket (64 slots of
32 nodes, 28 features), the mapper's narrow ``block_f`` of 8, and the
GAT aggregation at the whole-PubMed bucket (32,768 rows, ELL width 256);
and a training step's gradient through each kernel.  Every case carries the
kernels' per-row occupied widths (an SMEM block walked by a dynamic trip
count), so a form of them Mosaic refuses fails here.
The compiler refuses what interpret mode accepts
(unaligned blocks, vector-indexed gathers, more VMEM than a kernel may
use), so these tests guard the chip path without a chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels.fused_agg_cmb.ops as fused_ops
import repro.kernels.gat_agg.ops as gat_ops
import repro.kernels.spmm.ops as spmm_ops

CITESEER = dict(rows=4096, d=64)
CITESEER_FULL = dict(rows=4096, d=128)
IMDB = dict(rows=32 * 64, d=32)
MUTAG = dict(rows=32 * 64, d=8)
#: the whole-PubMed bucket of the published GAT: 19,717 nodes in 32,768
#: rows, its 171-neighbour hub setting ELL width 256
PUBMED = dict(rows=32768, d=256)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — any failure means no chip here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described chip's executables cannot be read back from the
    persistent cache; keep it off so nothing is written or warned."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def for_chip(monkeypatch, one_chip, no_compile_cache):
    """Shape factory on the described chip, with the wrappers told to
    compile the kernels instead of interpreting them (this process's own
    backend is the CPU)."""
    monkeypatch.setattr(spmm_ops, "default_interpret", lambda: False)
    monkeypatch.setattr(fused_ops, "default_interpret", lambda: False)
    monkeypatch.setattr(gat_ops, "default_interpret", lambda: False)

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return shape


def _ell(for_chip, rows, d):
    return for_chip(rows, d, dtype=jnp.int32), for_chip(rows, d)


@pytest.mark.parametrize(
    "bucket,f,block_v,block_f",
    [
        (CITESEER, 3703, 128, 128),  # seq/AC layer 1
        (CITESEER, 16, 128, 128),  # layer 2, and CA order's aggregation
        (MUTAG, 28, 128, 128),
        (CITESEER, 3703, 64, 8),  # mapper-emitted Vs(64)Fs(8)
        (CITESEER_FULL, 3703, 128, 128),
        (IMDB, 136, 128, 128),  # seq layer 1 of the imdb cells
        (IMDB, 16, 128, 128),
    ],
    ids=["citeseer-3703", "citeseer-16", "mutag-28", "citeseer-block_f8",
         "citeseer-full-3703", "imdb-136", "imdb-16"],
)
def test_spmm_compiles_for_v5e(for_chip, bucket, f, block_v, block_f):
    idx, wts = _ell(for_chip, **bucket)
    x = for_chip(bucket["rows"], f)
    compiled = jax.jit(
        lambda i, w, x: spmm_ops.spmm(i, w, x, block_v=block_v, block_f=block_f)
    ).lower(idx, wts, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "bucket,f,g,band,block_f",
    [
        (CITESEER, 3703, 16, 128, None),  # sp_opt/AC layer 1
        (CITESEER, 16, 6, 128, None),  # layer 2
        (MUTAG, 28, 16, 128, None),
        (CITESEER, 3703, 16, 64, 8),  # mapper-emitted Vs(64)Fs(8)
        (CITESEER_FULL, 3703, 16, 128, None),  # the citeseer-full bucket
        (CITESEER_FULL, 16, 6, 128, None),
        (IMDB, 136, 16, 128, None),
    ],
    ids=["citeseer-3703x16", "citeseer-16x6", "mutag-28x16", "citeseer-block_f8",
         "citeseer-full-3703x16", "citeseer-full-16x6", "imdb-136x16"],
)
def test_fused_agg_cmb_compiles_for_v5e(for_chip, bucket, f, g, band, block_f):
    idx, wts = _ell(for_chip, **bucket)
    x, w = for_chip(bucket["rows"], f), for_chip(f, g)
    compiled = jax.jit(
        lambda i, a, x, w: fused_ops.fused_agg_cmb(
            i, a, x, w, band_size=band, block_f=block_f
        )
    ).lower(idx, wts, x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["spmm", "fused_agg_cmb"])
def test_grad_step_compiles_for_v5e(for_chip, kernel):
    """A training step's value-and-grad through each kernel: the forward
    stays on the kernel, the backward is the jnp oracle's VJP."""
    idx, wts = _ell(for_chip, **MUTAG)
    x, w = for_chip(MUTAG["rows"], 28), for_chip(28, 16)

    def loss(x, w, i, a):
        if kernel == "spmm":
            h = spmm_ops.spmm(i, a, x, block_v=128, block_f=128) @ w
        else:
            h = fused_ops.fused_agg_cmb(i, a, x, w, band_size=128)
        return (h ** 2).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        x, w, idx, wts
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "heads,fh,block_v",
    [(8, 8, 32), (8, 3, 8), (8, 8, 128)],
    ids=["pubmed-layer1", "pubmed-layer2", "pubmed-block128"],
)
def test_gat_agg_compiles_for_v5e(for_chip, heads, fh, block_v):
    """The GAT aggregation at the whole-PubMed bucket: layer 1's [z | t]
    is 64 + 8 = 72 columns of 19,717 nodes, resident in VMEM."""
    idx, wts = _ell(for_chip, **PUBMED)
    z = for_chip(19717, heads * fh)
    s, t = for_chip(19717, heads), for_chip(19717, heads)
    compiled = jax.jit(
        lambda i, a, z, s, t: gat_ops.gat_agg(i, a, z, s, t, block_v=block_v)
    ).lower(idx, wts, z, s, t).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gat_grad_step_compiles_for_v5e(for_chip):
    """A training step's gradient through the GAT kernel (the backward is
    the jnp oracle's VJP)."""
    idx, wts = _ell(for_chip, **MUTAG)
    z, s, t = for_chip(MUTAG["rows"], 64), *(for_chip(MUTAG["rows"], 8),) * 2

    def loss(z, s, t, i, a):
        return (gat_ops.gat_agg(i, a, z, s, t, block_v=32) ** 2).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        z, s, t, idx, wts
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
