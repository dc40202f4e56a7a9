"""Multi-layer GNN models with per-layer multiphase dataflow schedules.

The execution path runs off the model-level schedule IR
(:class:`repro.core.schedule.ModelSchedule`): ``gnn_forward`` lowers each
layer's :class:`~repro.core.schedule.LayerSchedule` to its executable knobs
and dispatches :func:`repro.gnn.layers.multiphase_matmul` with them.

.. deprecated::
    Configuring execution through the ``GNNConfig.policy`` / ``order`` /
    ``band_size`` string knobs is deprecated.  They remain as a thin
    compatibility shim that constructs a homogeneous default schedule
    (:meth:`ModelSchedule.from_policies`) and emits a one-time
    :class:`DeprecationWarning`; new code should compile a
    :class:`repro.api.Program` with :func:`repro.compile` (or pass an
    explicit ``ModelSchedule``), so string-configured and mapper-searched
    models share one code path.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..core.schedule import ModelSchedule
from ..graphs.csr import CSRGraph
from ..kernels.common import resolve_use_pallas
from .layers import (
    DEFAULT_HEADS,
    LAST_AWARE,
    LAYER_FNS,
    EllAdjacency,
    init_layers,
    segment_readout,
)

#: set True after the first string-policy shim warning (reset by tests).
_POLICY_SHIM_WARNED = False


def _warn_policy_shim() -> None:
    """One-time DeprecationWarning for the string-policy execution path."""
    global _POLICY_SHIM_WARNED
    if not _POLICY_SHIM_WARNED:
        _POLICY_SHIM_WARNED = True
        warnings.warn(
            "executing from GNNConfig.policy/order/band_size string knobs is "
            "deprecated; compile a Program with repro.compile(...) or pass an "
            "explicit ModelSchedule (schedule=...) instead",
            DeprecationWarning,
            stacklevel=3,
        )


@dataclass(frozen=True)
class GNNConfig:
    kind: str = "gcn"  # gcn | sage | gin | gat
    f_in: int = 128
    hidden: int = 16  # Kipf-standard hidden width
    n_classes: int = 8
    n_layers: int = 2
    policy: str = "sp_opt"  # deprecated shim; see module docstring
    order: str = "AC"  # phase order
    band_size: int = 128
    use_pallas: bool | None = None  # Pallas kernels; None = on the TPU
    heads: int = DEFAULT_HEADS  # attention heads of a gat layer

    @property
    def dims(self) -> list[tuple[int, int]]:
        ds = []
        f = self.f_in
        for i in range(self.n_layers):
            out = self.n_classes if i == self.n_layers - 1 else self.hidden
            ds.append((f, out))
            f = out
        return ds

    def default_schedule(self) -> ModelSchedule:
        """The homogeneous ModelSchedule the (deprecated) string knobs
        stand for; prefer :func:`repro.compile` for new code."""
        return ModelSchedule.from_policies(
            self.policy, self.order, self.dims, band_size=self.band_size
        )


def init_gnn(cfg: GNNConfig, rng: jax.Array):
    return init_layers(cfg.kind, rng, cfg.dims, heads=cfg.heads)


def forward_layers(kind: str, params, adj: EllAdjacency, x: jax.Array,
                   specs, mesh=None, segment_ids=None, num_segments=None,
                   readout: str = "mean") -> jax.Array:
    """Run the layer stack under per-layer ExecSpecs (the single forward
    loop shared by ``gnn_forward`` and ``repro.api.Program.run``).

    With ``segment_ids`` / ``num_segments`` (a block-diagonally batched
    graph, see :mod:`repro.graphs.batching`), the per-node logits are
    reduced per member graph with :func:`repro.gnn.layers.segment_readout`
    and the result is (num_segments, f_out) — per-graph outputs, not one
    fused logit matrix.
    """
    fn = LAYER_FNS[kind]
    h = x
    for i, (layer, spec) in enumerate(zip(params, specs)):
        kw = {"last": i == len(params) - 1} if kind in LAST_AWARE else {}
        h = fn(layer, adj, h, spec=spec, mesh=mesh, **kw)
    if segment_ids is not None:
        if num_segments is None:
            raise ValueError("segment_ids needs num_segments")
        h = segment_readout(h, segment_ids, num_segments, reduce=readout)
    return h


def masked_xent_loss(logits: jax.Array, labels, mask):
    """Masked softmax cross-entropy shared by ``gnn_loss`` and
    ``Program.loss``."""
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def gnn_forward(
    cfg: GNNConfig,
    params,
    adj: EllAdjacency,
    x: jax.Array,
    mesh=None,
    schedule: ModelSchedule | None = None,
):
    """Forward pass under a model-level schedule.

    ``schedule`` defaults to the homogeneous schedule constructed from the
    config's string knobs (the **deprecated** shim path — it warns once);
    pass a mapper-searched :class:`~repro.core.schedule.ModelSchedule`
    (``search_model`` -> ``lower``), or better, compile a
    :class:`repro.api.Program` with :func:`repro.compile`, to run each
    layer under its own dataflow.
    """
    if schedule is None:
        _warn_policy_shim()
        schedule = cfg.default_schedule()
    if schedule.n_layers != len(params):
        raise ValueError(
            f"schedule has {schedule.n_layers} layers but params have "
            f"{len(params)}"
        )
    return forward_layers(
        cfg.kind, params, adj, x,
        schedule.lower(use_pallas=resolve_use_pallas(cfg.use_pallas)),
        mesh=mesh,
    )  # logits (V, n_classes)


def gnn_loss(cfg: GNNConfig, params, adj, x, labels, mask, schedule=None):
    logits = gnn_forward(cfg, params, adj, x, schedule=schedule)
    return masked_xent_loss(logits, labels, mask)


def make_node_classification_task(
    g: CSRGraph, f_in: int, n_classes: int, seed: int = 0
):
    """Seeded synthetic node-classification task over a CSR graph."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(g.n_nodes, f_in)).astype(np.float32)
    labels = rng.integers(0, n_classes, size=g.n_nodes).astype(np.int32)
    mask = (rng.random(g.n_nodes) < 0.3).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(labels), jnp.asarray(mask)
