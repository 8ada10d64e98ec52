"""jit'd public wrappers around the Pallas kernels.

Backend dispatch: model code reaches this module through
``kernels.dispatch`` (the KernelKey table — the engine's built-in
"pallas" backend and the calibrated analog backend both resolve their
kernels there, so the Pallas dependency stays optional and lazy). The
kernels lower natively on TPU; everywhere else they run Pallas
interpret mode (bit-exact semantics, executed on CPU), which is how
the correctness sweeps in tests/test_kernels.py and
tests/test_dispatch.py validate them against the integer oracles.
This module is the one place that makes that choice (``_lower``).

One wrapper per variant transfer:

  cim_matmul_kernel         P-8T per-plane coarse-fine flash (gpq)
  adder_tree_matmul_kernel  merged single-ADC conversion (2212.04320)
  cell_adc_matmul_kernel    in-array SAR per-row references (2307.05944)

``register_tuned_backend`` registers a "pallas-tuned" engine backend
with explicit block sizes, the hook a deployment uses to pin tiling
per shape without forking the dispatch code (per-shape pinning now
normally comes from ``kernels.autotune``'s cache instead).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.params import CIMConfig
from repro.core.pipeline import MacroSpec
from repro.kernels.cim_mac import (
    adder_tree_gpq_matmul,
    cell_adc_gpq_matmul,
    gpq_matmul,
)


def _lower(kernel, x_codes, w_codes, cfg, bm, bn, bk) -> jax.Array:
    """Run one GPQ kernel: native on TPU, the Pallas interpreter elsewhere.

    XLA cannot partition a Mosaic kernel, so on several devices it must
    run per device, inside a ``shard_map`` (``sharding.per_device``).
    """
    return kernel(
        x_codes, w_codes, cfg, bm=bm, bn=bn, bk=bk,
        interpret=jax.default_backend() != "tpu",
    ).astype(jnp.float32)


def cim_matmul_kernel(
    x_codes: jax.Array,
    w_codes: jax.Array,
    cfg: CIMConfig | MacroSpec,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
) -> jax.Array:
    """GPQ matmul via the Pallas kernel; drop-in for cim_matmul_int.

    The operating point may be a flat ``CIMConfig`` or a declarative
    ``MacroSpec`` — the kernel normalizes to the spec form and reads
    its stage fields. Noiseless by design (production inference path);
    Monte-Carlo noise analysis uses the jnp behavioral model.
    """
    return _lower(gpq_matmul, x_codes, w_codes, cfg, bm, bn, bk)


def adder_tree_matmul_kernel(
    x_codes: jax.Array,
    w_codes: jax.Array,
    cfg: CIMConfig | MacroSpec,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
) -> jax.Array:
    """Merged-transfer matmul (single-ADC adder tree) via Pallas.

    Drop-in for ``variants.adder_tree_matmul_int`` (noise off).
    """
    return _lower(adder_tree_gpq_matmul, x_codes, w_codes, cfg, bm, bn, bk)


def cell_adc_matmul_kernel(
    x_codes: jax.Array,
    w_codes: jax.Array,
    cfg: CIMConfig | MacroSpec,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
) -> jax.Array:
    """Cell-embedded-ADC (per-row-reference SAR) matmul via Pallas.

    Bit-identical to the floor transfer noise-free — drop-in for
    ``matmul.cim_matmul_int`` at a cell-adc operating point.
    """
    return _lower(cell_adc_gpq_matmul, x_codes, w_codes, cfg, bm, bn, bk)


def register_tuned_backend(
    *, bm: int = 128, bn: int = 128, bk: int = 128,
    name: str = "pallas-tuned",
) -> str:
    """Register an engine backend pinning the kernel's block sizes.

    Returns the backend key; select it per layer family via
    ``CIMPolicy(backend=<key>, mode='cim-kernel', ...)``. Routed
    through ``kernels.dispatch`` so the no-fallback guard and the
    resolution log see it like any other kernel execution.
    """
    from repro.core import engine  # lazy: engine lazily imports us too
    from repro.kernels import dispatch

    def _int_fn(x_codes, plan, cfg, key):
        del key  # kernel is noiseless by design
        return dispatch.dispatch(
            x_codes, plan.codes_i32, cfg,
            backend="pallas", block=(bm, bn, bk),
        )

    engine.register_backend(
        name, engine.quantized_backend(_int_fn), overwrite=True
    )
    return name
