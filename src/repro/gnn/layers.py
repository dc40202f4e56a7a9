"""GNN layers in JAX with explicit multiphase execution policies.

Each layer is a two-phase sparse/dense chain (aggregation = SpMM over the
padded-ELL adjacency, combination = GEMM).  The inter-phase dataflow is a
*program structure*:

  * ``seq``        — materialize the full V x F intermediate, then GEMM
                     (paper Seq: intermediate round-trips through memory).
  * ``sp_generic`` — `lax.scan` over row bands; each band's intermediate is
                     produced and consumed inside one scan step (paper
                     SP-Generic at row granularity).
  * ``sp_opt``     — the fused band step keeps the aggregated tile as the
                     immediate GEMM operand (no stacked intermediate at
                     all); on TPU this is the fused Pallas kernel
                     (:mod:`repro.kernels.fused_agg_cmb`), on CPU its jnp
                     body (paper SP-Optimized).
  * ``pp``         — producer/consumer device groups connected by
                     collective_permute (:mod:`repro.gnn.pp`), the paper's
                     Parallel Pipeline at the device level.

All policies compute the same numbers (tested to 1e-5); they differ in
where the intermediate lives — exactly the paper's point.

Phase order is a knob too: ``AC`` computes (A·X)·W, ``CA`` computes
A·(X·W) — same result, different cost (paper Sec. 3.3; AWB-GCN is CA).

Each executable path registers itself in the kernel registry
(:mod:`repro.core.registry`) keyed by the
:class:`~repro.core.schedule.ExecSpec` fields ``(policy, order,
use_pallas)``; :func:`multiphase_matmul` is a thin dispatcher that
normalizes its arguments into an ``ExecSpec`` and looks the path up.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import lookup_kernel, register_kernel
from ..core.schedule import ExecSpec
from ..graphs.csr import CSRGraph

POLICIES = ("seq", "sp_generic", "sp_opt", "pp")


@dataclass(frozen=True)
class EllAdjacency:
    """Device-side padded-ELL adjacency (see CSRGraph.to_ell)."""

    indices: jax.Array  # (V_pad, D) int32
    weights: jax.Array  # (V_pad, D) f32 — zero on padded slots
    n_nodes: int

    @classmethod
    def from_csr(
        cls, g: CSRGraph, block_rows: int = 1, pad_to: int | None = None
    ) -> "EllAdjacency":
        """``pad_to`` fixes the padded-ELL width D (>= the graph's max
        degree): batched serving pads every micro-batch of a bucket to the
        same D so rebinding never changes the device shapes."""
        if pad_to is not None and pad_to < g.max_degree:
            raise ValueError(
                f"pad_to={pad_to} is narrower than the graph's max degree "
                f"{g.max_degree}; neighbor lists would be truncated"
            )
        idx, wts = g.ell_arrays(block_rows, pad_to=pad_to)
        return cls(jnp.asarray(idx), jnp.asarray(wts), g.n_nodes)

    @classmethod
    def from_schedule(
        cls, g: CSRGraph, schedule, pad_to: int | None = None
    ) -> "EllAdjacency":
        """Build the adjacency with a ModelSchedule's lowered ELL block
        rows, so every layer's band scan walks aligned row groups."""
        return cls.from_csr(
            g, block_rows=schedule.ell_block_rows, pad_to=pad_to
        )

    @property
    def v_pad(self) -> int:
        return self.indices.shape[0]


# ---------------------------------------------------------------------------
# Aggregation (SpMM) primitives
# ---------------------------------------------------------------------------


def aggregate_full(adj: EllAdjacency, x: jax.Array) -> jax.Array:
    """Whole-graph aggregation: out[v] = sum_d w[v,d] * x[idx[v,d]]."""
    gathered = x[adj.indices]  # (V_pad, D, F)
    return jnp.einsum("vd,vdf->vf", adj.weights, gathered)


def aggregate_band(indices: jax.Array, weights: jax.Array, x: jax.Array) -> jax.Array:
    """Aggregation for one row band: indices/weights (B, D)."""
    gathered = x[indices]  # (B, D, F)
    return jnp.einsum("bd,bdf->bf", weights, gathered)


def _band_scan(
    adj: EllAdjacency,
    x: jax.Array,
    band_fn: Callable[[jax.Array], jax.Array],
    band_size: int,
):
    v_pad = adj.v_pad
    n_bands = -(-v_pad // band_size)
    pad = n_bands * band_size - v_pad
    idx = jnp.pad(adj.indices, ((0, pad), (0, 0)))
    wts = jnp.pad(adj.weights, ((0, pad), (0, 0)))
    idx = idx.reshape(n_bands, band_size, -1)
    wts = wts.reshape(n_bands, band_size, -1)

    def step(carry, band):
        i, w = band
        h_band = aggregate_band(i, w, x)
        return carry, band_fn(h_band)

    _, out = jax.lax.scan(step, None, (idx, wts))
    out = out.reshape(n_bands * band_size, -1)
    return out[:v_pad]


# ---------------------------------------------------------------------------
# Registered executable paths (keyed by ExecSpec fields)
# ---------------------------------------------------------------------------


@register_kernel("seq", orders=("AC",))
def _seq_ac(adj, x, w, spec, mesh):
    """Seq/AC: materialize the full aggregated intermediate, then GEMM."""
    return (aggregate_full(adj, x) @ w)[: adj.n_nodes]


@register_kernel("seq", orders=("CA",))
def _seq_ca(adj, x, w, spec, mesh):
    """Seq/CA: dense GEMM first, then whole-graph aggregation."""
    return aggregate_full(adj, x @ w)[: adj.n_nodes]


@register_kernel("seq", pallas=(True,))
def _seq_pallas(adj, x, w, spec, mesh):
    """Seq with the aggregation routed through the Pallas ELL SpMM."""
    from ..kernels.spmm.ops import spmm

    feats = x @ w if spec.order == "CA" else x
    h = spmm(
        adj.indices,
        adj.weights,
        feats,
        block_v=spec.band_size,
        block_f=spec.block_f or 128,
    )
    if spec.order == "CA":
        return h[: adj.n_nodes]
    return (h @ w)[: adj.n_nodes]


@register_kernel("sp_generic", orders=("AC",))
@register_kernel("sp_opt", orders=("AC",))
def _sp_ac(adj, x, w, spec, mesh):
    """SP/AC band scan: each band's intermediate lives inside one scan
    step, and the fused step keeps the aggregated tile as the immediate
    GEMM operand — the jnp body of both SP-Generic and SP-Optimized."""
    return _band_scan(adj, x, lambda h: h @ w, spec.band_size)[: adj.n_nodes]


@register_kernel("sp_generic", orders=("CA",))
@register_kernel("sp_opt", orders=("CA",))
def _sp_ca(adj, x, w, spec, mesh):
    """SP/CA: aggregate the combined features band by band."""
    return _band_scan(adj, x @ w, lambda h: h, spec.band_size)[: adj.n_nodes]


@register_kernel("sp_opt", orders=("AC",), pallas=(True,))
def _sp_opt_fused(adj, x, w, spec, mesh):
    """SP-Optimized/AC on TPU: the fused aggregation+combination kernel."""
    from ..kernels.fused_agg_cmb.ops import fused_agg_cmb

    return fused_agg_cmb(
        adj.indices,
        adj.weights,
        x,
        w,
        band_size=spec.band_size,
        block_f=spec.block_f,
    )[: adj.n_nodes]


@register_kernel("pp")
def _pp(adj, x, w, spec, mesh):
    """Parallel Pipeline: producer/consumer device groups (repro.gnn.pp)."""
    from .pp import pp_multiphase_matmul

    return pp_multiphase_matmul(
        adj, x, w, order=spec.order, mesh=mesh, band_size=spec.band_size
    )


# ---------------------------------------------------------------------------
# Two-phase execution under a multiphase policy
# ---------------------------------------------------------------------------

_SPEC_KNOBS = ("policy", "order", "band_size", "block_f", "use_pallas")


def _exec_spec(
    spec: ExecSpec | None,
    policy: str | None,
    order: str | None,
    band_size: int | None,
    block_f: int | None,
    use_pallas: bool | None,
    default_policy: str = "sp_opt",
    default_order: str = "AC",
) -> ExecSpec:
    """The one ExecSpec a layer runs under: ``spec`` when given (an
    explicit knob that disagrees with it raises :class:`ValueError`), else
    one built from the knobs (defaults: ``default_policy`` /
    ``default_order`` / band 128)."""
    if spec is not None:
        given = dict(
            policy=policy,
            order=order,
            band_size=band_size,
            block_f=block_f,
            use_pallas=use_pallas,
        )
        conflicts = {
            k: v
            for k, v in given.items()
            if v is not None and v != getattr(spec, k)
        }
        if conflicts:
            raise ValueError(
                f"got an ExecSpec plus conflicting explicit "
                f"kwargs {conflicts}; the spec has "
                f"{ {k: getattr(spec, k) for k in conflicts} } — pass one or "
                f"the other"
            )
        return spec
    return ExecSpec(
        policy=policy if policy is not None else default_policy,
        order=order if order is not None else default_order,
        band_size=band_size if band_size is not None else 128,
        block_f=block_f,
        use_pallas=bool(use_pallas),
    )


def multiphase_matmul(
    adj: EllAdjacency,
    x: jax.Array,
    w: jax.Array,
    policy: str | None = None,
    order: str | None = None,
    band_size: int | None = None,
    use_pallas: bool | None = None,
    mesh=None,
    block_f: int | None = None,
    spec: ExecSpec | None = None,
) -> jax.Array:
    """Execute aggregation + combination under an inter-phase policy.

    AC: (A @ X) @ W.  CA: A @ (X @ W).

    ``spec`` (a :class:`repro.core.schedule.ExecSpec`, the lowered form of a
    mapper-chosen :class:`~repro.core.schedule.LayerSchedule`) is the single
    source of truth when one is provided: passing an explicit ``policy`` /
    ``order`` / ``band_size`` / ``block_f`` / ``use_pallas`` kwarg that
    disagrees with the spec raises :class:`ValueError` rather than being
    silently ignored.  Without a spec, the string knobs build one
    (defaults: ``sp_opt`` / ``AC`` / band 128), so both entry styles
    dispatch through the same kernel registry.
    """
    spec = _exec_spec(spec, policy, order, band_size, block_f, use_pallas)
    kernel = lookup_kernel(spec.policy, spec.order, spec.use_pallas)
    return kernel(adj, x, w, spec, mesh)


# ---------------------------------------------------------------------------
# Segment-aware readout (batched serving)
# ---------------------------------------------------------------------------

READOUTS = ("sum", "mean", "max")


def segment_readout(
    h: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    reduce: str = "mean",
) -> jax.Array:
    """Per-graph readout over a block-diagonally batched node output.

    ``h`` is (V, F) node output of a batched forward pass and
    ``segment_ids[v]`` the member-graph index of row ``v``; returns the
    (num_segments, F) per-graph reduction.  Pad rows carry an id of
    ``num_segments`` (out of range), which JAX segment ops drop — so the
    batch padding never leaks into the readout.
    """
    if reduce not in READOUTS:
        raise ValueError(
            f"reduce must be one of {READOUTS}, got {reduce!r}"
        )
    if reduce == "max":
        return jax.ops.segment_max(h, segment_ids, num_segments=num_segments)
    s = jax.ops.segment_sum(h, segment_ids, num_segments=num_segments)
    if reduce == "sum":
        return s
    counts = jax.ops.segment_sum(
        jnp.ones(h.shape[0], h.dtype), segment_ids, num_segments=num_segments
    )
    return s / jnp.maximum(counts, 1.0)[:, None]


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def gcn_layer(params, adj, x, *, policy=None, order=None, **kw):
    """GCN: relu(Ã X W + b) with the multiphase policy."""
    out = multiphase_matmul(adj, x, params["w"], policy=policy, order=order, **kw)
    return jax.nn.relu(out + params["b"])


def sage_layer(params, adj, x, *, policy=None, order=None, **kw):
    """GraphSAGE with the paper's Sec.-6 decomposition:

        concat(X, A·X) @ W  ==  X @ W_top + (A·X) @ W_bottom

    The GEMM-first form keeps X @ W_top independent of aggregation — the
    extra scheduling freedom the paper highlights.
    """
    self_term = x[: adj.n_nodes] @ params["w_top"]
    agg_term = multiphase_matmul(
        adj, x, params["w_bottom"], policy=policy, order=order, **kw
    )
    return jax.nn.relu(self_term + agg_term + params["b"])


def gin_layer(params, adj, x, *, policy=None, order=None, **kw):
    """GIN: MLP((1 + eps) * x + sum-aggregate(x)).

    The sum aggregation is the same SpMM with unit weights; the first MLP
    matmul plays the combination role, so the multiphase policy applies.
    """
    eps = params["eps"]
    # aggregate-then-combine on the summed representation
    unit_adj = EllAdjacency(adj.indices, (adj.weights > 0).astype(x.dtype), adj.n_nodes)
    agg = multiphase_matmul(unit_adj, x, params["w1"], policy=policy, order=order, **kw)
    self_term = (1.0 + eps) * x[: adj.n_nodes] @ params["w1"]
    h = jax.nn.relu(agg + self_term + params["b1"])
    return jax.nn.relu(h @ params["w2"] + params["b2"])


def gat_layer(params, adj, x, *, policy=None, order=None, spec=None,
              band_size=None, use_pallas=None, mesh=None, block_f=None,
              last=False):
    """GAT (Velickovic et al., arXiv:1710.10903): z = X W with the heads
    side by side, per head h and row i

        alpha_ij = softmax_j LeakyReLU_0.2(z_i a_self[h] + z_j a_nbr[h])

    over the row's slots (self-loop included; the adjacency's weights act
    only as the edge mask), and out_i = sum_j alpha_ij z_j per head.  A
    hidden layer concatenates the heads and applies ELU, the last averages
    them (logits).  The combination runs first, since the scores need z:
    only a CA schedule that is not ``pp`` runs it.  The aggregation is the
    :mod:`repro.kernels.gat_agg` kernel with ``spec.use_pallas``, its jnp
    oracle otherwise; a row with no slot (batch padding) gives 0.  The
    knobs default to seq/CA.
    """
    spec = _exec_spec(spec, policy, order, band_size, block_f, use_pallas,
                      default_policy="seq", default_order="CA")
    if spec.order != "CA" or spec.policy == "pp":
        raise ValueError(
            f"a gat layer runs combination first (CA) and has no pp path; "
            f"got policy {spec.policy!r}, order {spec.order!r}"
        )
    heads, fh = params["a_self"].shape
    z = x @ params["w"]
    zh = z.reshape(-1, heads, fh)
    s = jnp.einsum("vhf,hf->vh", zh, params["a_self"])
    t = jnp.einsum("vhf,hf->vh", zh, params["a_nbr"])
    if spec.use_pallas:
        from ..kernels.gat_agg.ops import gat_agg

        o = gat_agg(adj.indices, adj.weights, z, s, t, block_v=spec.band_size)
    else:
        from ..kernels.gat_agg.ref import gat_agg_ref

        o = gat_agg_ref(adj.indices, adj.weights, z, s, t)
    o = o[: adj.n_nodes].astype(x.dtype)
    if last:
        return o.reshape(-1, heads, fh).mean(axis=1) + params["b"]
    return jax.nn.elu(o + params["b"])


LAYER_FNS = {"gcn": gcn_layer, "sage": sage_layer, "gin": gin_layer,
             "gat": gat_layer}

#: the layer kinds whose last layer differs from the others (``last=``)
LAST_AWARE = ("gat",)

#: attention heads of a ``gat`` layer unless told otherwise: the K = 8 of
#: the paper's citation-graph models
DEFAULT_HEADS = 8


def init_layer(kind: str, rng: jax.Array, f_in: int, f_out: int, *,
               heads: int = DEFAULT_HEADS, concat: bool = True):
    """One layer's parameters.  ``heads`` / ``concat`` matter only for
    ``gat`` (the last layer of a model averages its heads)."""
    k1, k2, k3 = jax.random.split(rng, 3)
    scale = 1.0 / np.sqrt(f_in)
    if kind == "gcn":
        return {
            "w": jax.random.normal(k1, (f_in, f_out)) * scale,
            "b": jnp.zeros((f_out,)),
        }
    if kind == "sage":
        return {
            "w_top": jax.random.normal(k1, (f_in, f_out)) * scale,
            "w_bottom": jax.random.normal(k2, (f_in, f_out)) * scale,
            "b": jnp.zeros((f_out,)),
        }
    if kind == "gin":
        return {
            "eps": jnp.zeros(()),
            "w1": jax.random.normal(k1, (f_in, f_out)) * scale,
            "b1": jnp.zeros((f_out,)),
            "w2": jax.random.normal(k2, (f_out, f_out)) * (1.0 / np.sqrt(f_out)),
            "b2": jnp.zeros((f_out,)),
        }
    if kind == "gat":
        # F', one head's width: a concatenating layer's output is the heads
        # side by side, an averaging one's is one head
        if concat and (heads < 1 or f_out % heads):
            raise ValueError(
                f"a gat layer that concatenates {heads} heads needs an "
                f"output width divisible by them, got {f_out}"
            )
        fh = f_out // heads if concat else f_out
        ka, kb = jax.random.split(k3)
        return {
            "w": jax.random.normal(k1, (f_in, heads * fh)) * scale,
            "a_self": jax.random.normal(ka, (heads, fh)) / np.sqrt(fh),
            "a_nbr": jax.random.normal(kb, (heads, fh)) / np.sqrt(fh),
            "b": jnp.zeros((f_out,)),
        }
    raise KeyError(kind)


def init_layers(kind: str, rng: jax.Array, dims, *,
                heads: int = DEFAULT_HEADS):
    """Parameters of a layer stack of ``(f_in, f_out)`` dims; only the
    last layer averages its heads."""
    keys = jax.random.split(rng, len(dims))
    return [
        init_layer(kind, k, fi, fo, heads=heads, concat=i < len(dims) - 1)
        for i, (k, (fi, fo)) in enumerate(zip(keys, dims))
    ]
