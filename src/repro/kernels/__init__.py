"""Pallas TPU kernels for the perf-critical hot spots.

Each kernel subpackage has kernel.py (pl.pallas_call + BlockSpec),
ops.py (jit'd wrapper) and ref.py (pure-jnp oracle).  Kernels run
compiled on a TPU and in the Pallas interpreter on the CPU, chosen from
the platform by `platform.pallas_interpret`.
"""
