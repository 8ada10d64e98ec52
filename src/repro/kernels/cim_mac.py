"""Pallas TPU kernels for the GPQ (grouped-partial-sum quantized) matmul.

Three variant transfers share one tiling scheme (see below): the P-8T
per-plane flash (``gpq_matmul``), the adder-tree merged single-ADC
conversion (``adder_tree_gpq_matmul``), and the cell-embedded SAR
readout (``cell_adc_gpq_matmul``). ``kernels.dispatch`` routes each
macro variant to its kernel; the notes below describe the shared
structure through the P-8T instance.

This is the perf-critical hot spot of the paper's technique mapped to
TPU: the 16-row ABL charge-sharing accumulation becomes a grouped
contraction, and the ADC transfer (cutoff clip + floor quantization +
bit-plane shift-add) is fused onto the partial-sum tile while it lives
in VMEM -- one HBM round trip per output tile instead of one per
(group x bit-plane) intermediate, which is what the naive jnp
formulation pays.

Tiling (BlockSpec):
  grid = (M/bm, N/bn, K/bk), k innermost ("arbitrary" semantics so the
  output tile accumulates across k steps).
  x tile   [bm, bk]   activation codes in their NATIVE integer dtype
                      (i32 from quantize_acts; widened to f32 inside
                      the tile — the HBM->VMEM stream stays narrow)
  w tile   [bk, bn]   weight codes: i8/i32 signed plan codes OR a
                      plan's packed-plane bytes (u8) — plane b is bit b
                      of the widened value either way (sign extension
                      and zero extension agree on the low weight_bits),
                      so both storage forms lower through one kernel
  out tile [bm, bn]   f32 accumulated shift-add results

  ``kernels.dispatch`` sizes the tile to the call when no block is
  pinned: bm = min(128, M rounded up to 8), so a batch-32 decode step
  runs 32-row tiles instead of 128 rows of which 96 are padding; bn
  widens (256, 512) only as bm shrinks, keeping bm * bn <= 128 * 128
  (the out/ADC tile never grows); bk = 128 rounded down to a multiple
  of rows_active. The result does not depend on the tiling: each
  output accumulates the same (group, plane) codes in the same k order.

Inside one k step the kernel extracts the B two's-complement planes of
the w tile as [bk, bn] 0/1 tiles and runs, per 16-row group g and plane
b, one MXU contraction
  x[:, g*rows:(g+1)*rows] @ plane_b[g*rows:(g+1)*rows]  -> [bm, bn]
followed by the ADC nonlinearity and the shift-add into the output
tile. Groups and planes are static Python loops: Mosaic lowers static
slices and 2-D dots, whereas the batched form ([gk, bm, rows] x
[gk, rows, B*bn]) needs a lane-dim reshape it refuses, and a traced
per-plane sign vector would be a captured array constant. Plane weights
are Python floats folded into the code.

The MXU sees a contraction depth of rows (16): that granularity is
*semantic* -- the ADC sits between 16-row groups, so deeper contraction
would change the computed function. This bounds achievable MXU
utilization at rows/128 for the faithful mode.

f32 accumulation is exact for integers < 2**24; with |contrib| per
(group, plane) <= 2**(B-1) * threshold the wrapper requires
K / rows * 2**(B-1) * threshold < 2**24 (K <~ 16k at the paper op point)
and raises ``KernelInfeasible`` beyond that.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.params import CIMConfig
from repro.core.pipeline import MacroSpec
from repro.kernels.ref import KernelInfeasible, _plane_sign, pmac_precision


def _tile_planes(x_ref, w_ref, weight_bits: int):
    """Widen one (x, w) tile pair: f32 codes plus B [bk, bn] 0/1 planes.

    Widening to f32/i32 happens here, on the VMEM-resident tile, not on
    the HBM operands. Bit b of the i32-widened weight is the weight's
    two's-complement bit b for i8/i32 codes (sign extension) and for
    packed u8 plane bytes (zero extension) alike.
    """
    x = x_ref[...].astype(jnp.float32)
    u = w_ref[...].astype(jnp.int32)
    planes = [
        jnp.bitwise_and(jnp.right_shift(u, b), 1).astype(jnp.float32)
        for b in range(weight_bits)
    ]
    return x, planes


def _group_pmacs(x, planes, g: int, rows: int, precision):
    """Plane partial MACs of row group ``g``: B tiles of [bm, bn] f32.

    Static lane/sublane slices of the group's rows and one 2-D MXU
    contraction per plane (at ``ref.pmac_precision``); the results are
    exact integers.
    """
    # One 0/1-plane group contraction is a pMAC: exact in f32 as long
    # as the worst group partial sum clears the mantissa with room.
    # bound(CIM601): pmac_max < 2**24
    lo, hi = g * rows, (g + 1) * rows
    xg = x[:, lo:hi]
    return [
        jnp.dot(
            xg, p[lo:hi],
            precision=precision,
            preferred_element_type=jnp.float32,
        )
        for p in planes
    ]


def _gpq_kernel(
    x_ref,
    w_ref,
    out_ref,
    *,
    rows: int,
    weight_bits: int,
    precision: jax.lax.Precision,
    adc_step: float,
    adc_codes: int,
    nearest: bool = False,
):
    """One (i, j, k) grid step; accumulates into out_ref."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x, planes = _tile_planes(x_ref, w_ref, weight_bits)
    # Fused ADC transfer: cutoff clip + floor (or round-to-nearest)
    # quantization, then the digital shift-add with the MSB plane
    # negative (two's complement).
    half = 0.5 if nearest else 0.0
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for g in range(x.shape[1] // rows):
        for b, pmac in enumerate(_group_pmacs(x, planes, g, rows, precision)):
            code = jnp.clip(jnp.floor(pmac / adc_step + half), 0, adc_codes - 1)
            acc = acc + code * (_plane_sign(b, weight_bits) * adc_step)
    out_ref[...] += acc


def _adder_tree_kernel(
    x_ref,
    w_ref,
    out_ref,
    *,
    rows: int,
    weight_bits: int,
    precision: jax.lax.Precision,
    step: float,
    code_min: int,
    code_max: int,
    nearest: bool,
):
    """Merged-transfer grid step (single-ADC adder-tree interface).

    The per-plane partial MACs of each row group fold through the
    binary-weighted analog adder (MSB negative) into ONE signed merged
    value per (group, output); the single SAR conversion is the fused
    quantizer here — one code per group instead of B.
    """
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x, planes = _tile_planes(x_ref, w_ref, weight_bits)
    half = 0.5 if nearest else 0.0
    codes = jnp.zeros(out_ref.shape, jnp.float32)
    for g in range(x.shape[1] // rows):
        # Charge-domain merge of the group's plane pMACs.
        merged = None
        for b, pmac in enumerate(_group_pmacs(x, planes, g, rows, precision)):
            term = pmac * _plane_sign(b, weight_bits)
            merged = term if merged is None else merged + term
        codes = codes + jnp.clip(
            jnp.floor(merged / step + half), code_min, code_max
        )
    # Zero-padded groups merge to 0 -> code 0 -> no contribution, so K
    # padding stays benign. Codes are exact integers; the common factor
    # `step` is applied after the group reduction.
    out_ref[...] += codes * step


def _cell_adc_kernel(
    x_ref,
    w_ref,
    out_ref,
    *,
    rows: int,
    weight_bits: int,
    precision: jax.lax.Precision,
    adc_step: float,
    adc_bits: int,
    nearest: bool,
):
    """Cell-embedded-ADC grid step: SAR search vs per-row references.

    The conversion is expressed exactly as the hardware does it — a
    successive-approximation binary search of one reused comparator per
    column against the in-array per-row reference levels (level t sits
    at pMAC t*step) — instead of the flash model's floor division. The
    resulting codes are bit-identical to the floor transfer (the
    variant's integer oracle), which the dispatch parity tests assert.
    """
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x, planes = _tile_planes(x_ref, w_ref, weight_bits)
    # 'nearest' shifts every decision threshold by half an LSB; 'floor'
    # compares against the reference levels directly.
    thresh_off = 0.5 * adc_step if nearest else 0.0
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for g in range(x.shape[1] // rows):
        for b, pmac in enumerate(_group_pmacs(x, planes, g, rows, precision)):
            code = jnp.zeros(pmac.shape, dtype=jnp.int32)
            for bit in range(adc_bits - 1, -1, -1):  # static SAR loop
                trial = jnp.bitwise_or(code, 1 << bit)
                take = pmac + thresh_off >= trial.astype(jnp.float32) * adc_step
                code = jnp.where(take, trial, code)
            acc = acc + code.astype(jnp.float32) * (
                _plane_sign(b, weight_bits) * adc_step
            )
    out_ref[...] += acc


def _tiled_call(kernel, x_codes, w_codes, *, bm, bn, bk, interpret):
    """Shared pad-to-tiles + pallas_call plumbing of the GPQ kernels.

    Shapes are padded to tile multiples; K padding is benign for every
    transfer here (zero codes -> zero pMAC/merged value -> code 0 -> no
    shift-add contribution). Operands pad in their NATIVE dtypes — an
    i8/u8 weight tensor streams 1 byte per weight into VMEM and the
    kernel widens in-tile; the old up-front f32 cast moved 4x the
    bytes every call.
    """
    m, k = x_codes.shape
    n = w_codes.shape[1]
    mp = -(-m // bm) * bm
    np_ = -(-n // bn) * bn
    kp = -(-k // bk) * bk
    x_p = jnp.pad(x_codes, ((0, mp - m), (0, kp - k)))
    w_p = jnp.pad(w_codes, ((0, kp - k), (0, np_ - n)))

    out = pl.pallas_call(
        kernel,
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
        # TPU compiler hints: m/n parallel, k sequential (accumulation).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(x_p, w_p)
    return out[:m, :n]


def _check_blocking(bk: int, rows: int) -> None:
    if bk % rows != 0:
        raise ValueError(f"bk={bk} must be a multiple of rows_active={rows}")


@functools.partial(
    jax.jit, static_argnames=("cfg", "bm", "bn", "bk", "interpret")
)
def gpq_matmul(
    x_codes: jax.Array,
    w_codes: jax.Array,
    cfg: CIMConfig | MacroSpec,
    *,
    interpret: bool,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
) -> jax.Array:
    """Pallas GPQ matmul. x: [M, K] codes, w: [K, N] signed codes.

    The operating point is consumed as a declarative ``MacroSpec``
    (``CIMConfig`` inputs are normalized): the kernel reads the AMU
    group geometry (``rows_active``) and the ADC transfer constants
    (``adc_step``/``adc_codes``/``threshold``) from the stage specs
    rather than raw config fields, so swept/calibrated specs lower
    without a config round-trip. ``interpret`` has no default: native
    Mosaic lowering or the Pallas interpreter is the caller's choice
    (``kernels.ops`` decides it from the backend).
    """
    cfg = MacroSpec.from_config(cfg)
    m, k = x_codes.shape
    k2, n = w_codes.shape
    assert k == k2, (x_codes.shape, w_codes.shape)
    rows = cfg.rows_active
    _check_blocking(bk, rows)
    # f32 exact-integer accumulation bound (see module docstring). The
    # static mirror proves it over every registered contraction depth:
    # bound(CIM601): G * 2**(weight_bits - 1) * threshold < 2**23 * adc_step
    max_abs = (k + rows - 1) // rows * (1 << (cfg.weight_bits - 1)) * cfg.threshold
    if max_abs >= (1 << 24) * 0.5 * cfg.adc_step:
        raise KernelInfeasible(
            f"K={k} too deep for exact f32 accumulation at this operating "
            "point; use core.matmul.cim_matmul_int"
        )

    kernel = functools.partial(
        _gpq_kernel,
        rows=rows,
        weight_bits=cfg.weight_bits,
        precision=pmac_precision(cfg.act_bits),
        adc_step=float(cfg.adc_step),
        adc_codes=cfg.adc_codes,
        nearest=cfg.adc_mode == "nearest",
    )
    return _tiled_call(
        kernel, x_codes, w_codes, bm=bm, bn=bn, bk=bk, interpret=interpret
    )


@functools.partial(
    jax.jit, static_argnames=("cfg", "bm", "bn", "bk", "interpret")
)
def adder_tree_gpq_matmul(
    x_codes: jax.Array,
    w_codes: jax.Array,
    cfg: CIMConfig | MacroSpec,
    *,
    interpret: bool,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
) -> jax.Array:
    """Pallas kernel for the adder-tree merged transfer (arXiv:2212.04320).

    Per row group the B plane partial-MACs fold through the binary-
    weighted charge-domain adder into one signed merged value, and ONE
    conversion (``bits_eff`` SAR decisions) produces the group's code —
    the single-ADC interface of ``variants.adder_tree_matmul_int``,
    fused onto the contraction tile. Noiseless by design (production
    inference path); bit-exact vs the integer transfer (dispatch parity
    tests).
    """
    from repro.core.variants import merged_quant  # noqa: PLC0415 - no cycle

    cfg = MacroSpec.from_config(cfg)
    m, k = x_codes.shape
    assert k == w_codes.shape[0], (x_codes.shape, w_codes.shape)
    rows = cfg.rows_active
    _check_blocking(bk, rows)
    mq = merged_quant(cfg)
    # f32 exactness: group codes are integers in [code_min, code_max];
    # the accumulated code sum must stay exactly representable.
    # bound(CIM601): G * max(-code_min, code_max) < 2**24
    g = (k + rows - 1) // rows
    if g * max(abs(mq.code_min), mq.code_max) >= (1 << 24):
        raise KernelInfeasible(
            f"K={k} too deep for exact f32 accumulation of merged codes; "
            "use variants.adder_tree_matmul_int"
        )

    kernel = functools.partial(
        _adder_tree_kernel,
        rows=rows,
        weight_bits=cfg.weight_bits,
        precision=pmac_precision(cfg.act_bits),
        step=float(mq.step),
        code_min=mq.code_min,
        code_max=mq.code_max,
        nearest=cfg.adc_mode == "nearest",
    )
    return _tiled_call(
        kernel, x_codes, w_codes, bm=bm, bn=bn, bk=bk, interpret=interpret
    )


@functools.partial(
    jax.jit, static_argnames=("cfg", "bm", "bn", "bk", "interpret")
)
def cell_adc_gpq_matmul(
    x_codes: jax.Array,
    w_codes: jax.Array,
    cfg: CIMConfig | MacroSpec,
    *,
    interpret: bool,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
) -> jax.Array:
    """Pallas kernel for the cell-embedded ADC readout (arXiv:2307.05944).

    Same grouping as :func:`gpq_matmul`, but the conversion is the
    in-array SAR search of one reused comparator per column against the
    per-row cell-generated reference levels — ``adc_bits`` unrolled
    compare/keep decisions instead of a floor division. Noise-free
    codes are bit-identical to the P-8T floor transfer (the variant's
    ideal transfer; asserted in the dispatch parity tests).
    """
    cfg = MacroSpec.from_config(cfg)
    m, k = x_codes.shape
    assert k == w_codes.shape[0], (x_codes.shape, w_codes.shape)
    rows = cfg.rows_active
    _check_blocking(bk, rows)
    # Same accumulation budget as gpq_matmul (the SAR codes are the
    # same integers the floor transfer produces).
    # bound(CIM601): G * 2**(weight_bits - 1) * threshold < 2**23 * adc_step
    max_abs = (k + rows - 1) // rows * (1 << (cfg.weight_bits - 1)) * cfg.threshold
    if max_abs >= (1 << 24) * 0.5 * cfg.adc_step:
        raise KernelInfeasible(
            f"K={k} too deep for exact f32 accumulation at this operating "
            "point; use core.matmul.cim_matmul_int"
        )

    kernel = functools.partial(
        _cell_adc_kernel,
        rows=rows,
        weight_bits=cfg.weight_bits,
        precision=pmac_precision(cfg.act_bits),
        adc_step=float(cfg.adc_step),
        adc_bits=cfg.adc_bits,
        nearest=cfg.adc_mode == "nearest",
    )
    return _tiled_call(
        kernel, x_codes, w_codes, bm=bm, bn=bn, bk=bk, interpret=interpret
    )
