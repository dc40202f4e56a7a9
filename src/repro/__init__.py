"""repro — multiphase sparse/dense dataflows (Garg et al. 2021) as a
JAX/TPU framework.  See README.md / DESIGN.md / EXPERIMENTS.md.

The front door is :func:`repro.compile`: search a model-level dataflow
schedule (or accept one), lower it to executable kernel knobs, and get a
frozen :class:`repro.api.Program` with ``run``/``loss``/``stats`` and a
cacheable ``save``/``load`` JSON artifact.
"""
from .api import (
    Program,
    compile,
    compile_count,
    trace_count,
    workload_fingerprint,
)
from .core.hw import LatencyModel

__all__ = [
    "LatencyModel",
    "Program",
    "compile",
    "compile_count",
    "trace_count",
    "workload_fingerprint",
]
