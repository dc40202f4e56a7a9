from .layers import (
    DEFAULT_HEADS,
    EllAdjacency,
    LAYER_FNS,
    POLICIES,
    aggregate_full,
    gat_layer,
    gcn_layer,
    gin_layer,
    init_layer,
    init_layers,
    multiphase_matmul,
    sage_layer,
    segment_readout,
)
from .model import (
    GNNConfig,
    forward_layers,
    gnn_forward,
    gnn_loss,
    init_gnn,
    make_node_classification_task,
    masked_xent_loss,
)
