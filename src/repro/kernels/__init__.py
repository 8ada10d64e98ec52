"""Pallas TPU kernels for the CIM hot spots.

cim_mac.py  : GPQ (grouped-partial-sum quantized) matmuls — the macro's
              16-row ABL accumulation + fused variant transfers (P-8T
              flash, adder-tree merged single-ADC, cell-embedded SAR),
              VMEM-tiled.
ops.py      : jit'd wrappers; the one place that decides native
              Mosaic lowering (TPU) vs Pallas interpret mode.
ref.py      : pure-jnp vectorized oracles, doubling as the dispatch
              table's "ref" backend.
dispatch.py : the KernelKey(variant, backend, shape_cell, dtype) ->
              implementation table every macro matmul routes through
              (``from repro.kernels import dispatch`` — module import;
              the entry point is ``dispatch.dispatch``).
autotune.py : per-(arch, variant, shape-cell) backend/block sweeps with
              the persistent results/autotune/<arch>.json cache.
"""

from repro.kernels.cim_mac import (
    adder_tree_gpq_matmul,
    cell_adc_gpq_matmul,
    gpq_matmul,
)
from repro.kernels.dispatch import KernelKey, register_kernel
from repro.kernels.ops import (
    adder_tree_matmul_kernel,
    cell_adc_matmul_kernel,
    cim_matmul_kernel,
)
from repro.kernels.ref import (
    KernelInfeasible,
    adder_tree_matmul_ref,
    cim_matmul_ref,
)

__all__ = [
    "KernelInfeasible",
    "KernelKey",
    "adder_tree_gpq_matmul",
    "adder_tree_matmul_kernel",
    "adder_tree_matmul_ref",
    "cell_adc_gpq_matmul",
    "cell_adc_matmul_kernel",
    "cim_matmul_kernel",
    "cim_matmul_ref",
    "gpq_matmul",
    "register_kernel",
]
