from .kernel import gat_agg_kernel
from .ops import gat_agg
from .ref import gat_agg_ref
