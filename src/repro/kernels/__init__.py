"""Pallas TPU kernels for the performance-critical phases.

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jitted
wrapper, compiled on the TPU and interpreted on the CPU only), ref.py
(pure-jnp oracle).
"""
