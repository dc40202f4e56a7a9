"""Parallel-Pipeline (PP) inter-phase dataflow at the device level.

The paper's PP splits the PE array into an aggregation engine and a
combination engine connected by a ping-pong buffer (HyGCN/AWB-GCN style).
The TPU-native analogue implemented here splits the *device mesh* into two
phase groups: group 0 aggregates row band ``i`` while group 1 runs the
combination GEMM on band ``i-1``; the intermediate band is handed off with
``collective_permute`` (the "NoC connecting Agg and Cmb units", Table 2).

This is the honest mapping of the paper's spatial phase partitioning onto
jax-native constructs — no torch.distributed emulation, just shard_map +
lax collectives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def mesh_devices(
    mesh: jax.sharding.Mesh | None = None,
    devices: list | None = None,
) -> list:
    """Flatten a placement target into an ordered device list.

    Accepts a :class:`jax.sharding.Mesh` (any axis shape — placement is
    over the flattened device grid), an explicit device list, or neither
    (all local devices).  The serving scheduler and the PP path share this
    so "the mesh" means the same devices in both.
    """
    if mesh is not None and devices is not None:
        raise ValueError("pass mesh= or devices=, not both")
    if mesh is not None:
        return list(mesh.devices.flat)
    if devices is not None:
        return list(devices)
    return list(jax.devices())


def pp_multiphase_matmul(
    adj,
    x: jax.Array,
    w: jax.Array,
    order: str = "AC",
    mesh: jax.sharding.Mesh | None = None,
    band_size: int = 128,
    phase_axis: str = "phase",
) -> jax.Array:
    """(A @ X) @ W (AC) or A @ (X @ W) (CA) on a two-group phase mesh.

    Falls back to the SP-Generic band scan when no multi-device mesh is
    available (the CPU test container has one device; the PP structure is
    exercised with ``--xla_force_host_platform_device_count`` in
    tests/test_gnn_pp.py and examples/gnn_parallel_pipeline.py).
    """
    if mesh is None or mesh.devices.size < 2:
        from .layers import multiphase_matmul

        return multiphase_matmul(adj, x, w, policy="sp_generic", order=order)

    if order == "CA":
        # combination first is a single dense GEMM; pipeline the aggregation
        # of its output bands instead (AWB-GCN direction).  sp_generic/CA is
        # exactly that band scan — routing through the AC path with an
        # identity W would pay a pointless O(V*G^2) GEMM per band.
        from .layers import multiphase_matmul

        return multiphase_matmul(
            adj, x, w, policy="sp_generic", order="CA", band_size=band_size
        )

    v_pad = adj.v_pad
    n_bands = -(-v_pad // band_size)
    pad = n_bands * band_size - v_pad
    idx = jnp.pad(adj.indices, ((0, pad), (0, 0))).reshape(n_bands, band_size, -1)
    wts = jnp.pad(adj.weights, ((0, pad), (0, 0))).reshape(n_bands, band_size, -1)

    def pipelined(idx, wts, x, w):
        p = jax.lax.axis_index(phase_axis)
        f_in, g_out = w.shape

        def agg(band_i):
            g = x[idx[band_i]]  # (B, D, F)
            return jnp.einsum("bd,bdf->bf", wts[band_i], g)

        def step(carry, band_i):
            prev_band = carry  # intermediate band produced last step
            # producer group computes band i; consumer sees zeros
            h = jnp.where(p == 0, agg(band_i), jnp.zeros((band_size, f_in), x.dtype))
            # hand off through the pipeline "NoC"
            h_next = jax.lax.ppermute(h, phase_axis, perm=[(0, 1)])
            # consumer group combines the band received in the *previous*
            # step (one-deep ping-pong buffer)
            out = jnp.where(
                p == 1, prev_band @ w, jnp.zeros((band_size, g_out), x.dtype)
            )
            return h_next, out

        carry0 = jnp.zeros((band_size, f_in), x.dtype)
        carry, outs = jax.lax.scan(step, carry0, jnp.arange(n_bands))
        # drain: the last band is still in the consumer's buffer
        last = jnp.where(p == 1, carry @ w, jnp.zeros((band_size, g_out), x.dtype))
        outs = jnp.concatenate([outs[1:], last[None]], axis=0)
        # only the consumer group holds real outputs; share them
        outs = jax.lax.psum(outs, phase_axis)
        return outs.reshape(n_bands * band_size, g_out)

    shard = jax.shard_map(
        pipelined,
        mesh=mesh,
        in_specs=(P(), P(), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    return shard(idx, wts, x, w)[: adj.n_nodes]
