"""Pure-jnp oracle for the GPQ (grouped-partial-sum quantized) matmul.

Independent of core/matmul.py's scan formulation on purpose: this is the
vectorized "textbook" statement of the macro semantics used to
cross-validate both the behavioral model and the Pallas kernels.

  pmac[m, g, b, n] = sum_{k in group g} x[m, k] * bit_b(w[k, n])
  code             = clip(floor(pmac / step), 0, 2**adc_bits - 1)
  y[m, n]          = sum_{g, b} sign_b * step * code

Noiseless by definition (the kernels are the production path; hardware-
error Monte-Carlo runs through core.matmul.cim_matmul_int).

Beyond oracle duty these formulations are also the dispatch table's
"ref" backend: at decode shapes (small M) the single fused einsum pair
beats the scan's G sequential group steps on CPU/GPU, which is exactly
the per-shape choice ``kernels.autotune`` discovers and pins. For that
role they accept a plan's pre-grouped ``planes`` (both storage forms)
so the weight side stays stationary.
"""

from __future__ import annotations

from typing import Iterator

import jax
import jax.numpy as jnp

from repro.core.params import CIMConfig
from repro.core.quant import bitslice_weights, plane_signs, slot_spec

# Every f32 contraction here carries exact integers (or integer codes
# times the ADC step). TPU's DEFAULT precision rounds f32 operands to
# bf16 (8 significant bits); slot words (up to ~2**16), signed plane
# sums and codes times a general ADC step do not survive that, so those
# contractions take HIGHEST.
_EXACT = jax.lax.Precision.HIGHEST


def pmac_precision(act_bits: int) -> jax.lax.Precision:
    """Matmul precision of a pMAC: activation codes against 0/1 planes.

    Codes below 2**8 are exact in bf16, so the single-pass DEFAULT
    loses nothing there (accumulation is f32 either way); wider codes
    take HIGHEST.
    """
    return jax.lax.Precision.DEFAULT if act_bits <= 8 else _EXACT


class KernelInfeasible(ValueError):
    """A kernel cannot run this shape / operating point / operand form.

    Raised by the implementations' own feasibility guards (the f32
    exact-accumulation depth limits, spread-slot geometry and operand
    checks). ``dispatch`` falls back to the scan transfer on this error
    alone, when the implementation was chosen implicitly; any other
    exception — a kernel the compiler refuses, say — propagates.
    """


def _grouped_operands(x_codes, w_codes, cfg, planes):
    """Normalize (w_codes | plan planes) -> xg [M,G,rows], wp [B,G,rows,N]."""
    m, k = x_codes.shape
    rows = cfg.rows_active
    b = cfg.weight_bits
    k_pad = -(-k // rows) * rows
    g = k_pad // rows
    x = jnp.pad(x_codes.astype(jnp.float32), ((0, 0), (0, k_pad - k)))
    xg = x.reshape(m, g, rows)
    if planes is None:
        n = w_codes.shape[1]
        w = jnp.pad(w_codes.astype(jnp.int32), ((0, k_pad - k), (0, 0)))
        wp = bitslice_weights(w, b).reshape(b, g, rows, n)
    elif planes.ndim == 3:  # packed plan planes: [G, rows, N] uint8
        wp = bitslice_weights(planes, b)  # [B, G, rows, N]
    else:  # unpacked plan planes: [G, B, rows, N]
        wp = planes.transpose(1, 0, 2, 3)
    return xg, wp.astype(jnp.float32)


def cim_matmul_ref(
    x_codes: jax.Array,
    w_codes: jax.Array,
    cfg: CIMConfig,
    *,
    planes: jax.Array | None = None,
) -> jax.Array:
    """[M, K] x [K, N] -> [M, N] float32, macro semantics, vectorized.

    ``planes`` optionally reuses a plan's pre-grouped bit planes
    (``engine.plan_weights`` layouts, grouped at ``cfg.rows_active``)
    instead of re-slicing ``w_codes``.
    """
    xg, wp = _grouped_operands(x_codes, w_codes, cfg, planes)
    pmac = jnp.einsum("mgr,bgrn->mgbn", xg, wp,
                      precision=pmac_precision(cfg.act_bits))
    half = 0.5 if getattr(cfg, "adc_mode", "floor") == "nearest" else 0.0
    code = jnp.clip(
        jnp.floor(pmac / cfg.adc_step + half), 0, cfg.adc_codes - 1
    )
    signs = plane_signs(cfg.weight_bits).astype(jnp.float32)
    return jnp.einsum(
        "mgbn,b->mn", code * cfg.adc_step, signs, precision=_EXACT
    )


def adder_tree_matmul_ref(
    x_codes: jax.Array,
    w_codes: jax.Array,
    cfg: CIMConfig,
    *,
    planes: jax.Array | None = None,
) -> jax.Array:
    """Vectorized single-ADC merged transfer (adder-tree interface).

    The textbook statement of ``variants.adder_tree_matmul_int``: merge
    the plane partial-MACs in the charge domain (MSB negative), ONE
    conversion per (group, output), sum the dequantized group codes.
    Noiseless; bit-exact vs the scan transfer (dispatch parity tests).
    """
    from repro.core.variants import merged_quant  # noqa: PLC0415 - no cycle

    spec = cfg
    xg, wp = _grouped_operands(x_codes, w_codes, cfg, planes)
    signs = plane_signs(cfg.weight_bits).astype(jnp.float32)
    pmac = jnp.einsum("mgr,bgrn->mgbn", xg, wp,
                      precision=pmac_precision(cfg.act_bits))
    merged = jnp.einsum("mgbn,b->mgn", pmac, signs, precision=_EXACT)
    mq = merged_quant(spec)
    half = 0.5 if getattr(spec, "adc_mode", "floor") == "nearest" else 0.0
    code = jnp.clip(
        jnp.floor(merged / mq.step + half), mq.code_min, mq.code_max
    )
    return jnp.sum(code, axis=1) * mq.step


# ---------------------------------------------------------------------------
# Spread-slot formulations (the decode-shape "slots" backend)
# ---------------------------------------------------------------------------
#
# The unpacked f32 plane tensor moves 4*B bytes per weight through the
# dot — at decode shapes (M ~ 1) that memory traffic IS the runtime.
# ``quant.spread_slots`` packs ``per_slot`` bit planes per f32 at a
# stride wide enough that every per-plane group pMAC occupies its own
# exact integer field of the combined dot product (all partial sums
# stay < 2**24, so f32 accumulation is exact); one batched contraction
# then yields ALL plane pMACs and the epilogue recovers them with
# floor/multiply field extraction. At the paper point this is 12 bytes
# of weight traffic per weight instead of 32 — measured ~5x faster than
# the unpacked ref at the LM decode cell, within ~4x of the pure int8
# exact matmul. Bit-exact vs the scan/ref transfers (parity-tested).


def _slot_dot(x_codes, slots, spec):
    """[M, K] codes x [G, rows, S*N] slots -> combined [G, M, S*N] f32."""
    # The combined dot is exact iff the fully-saturated packed partial
    # sum stays inside the f32 mantissa (same series as spread_slots).
    # bound(CIM601): pmac_max * (stride**per_slot - 1) // (stride - 1) < 2**24
    m, k = x_codes.shape
    g, rows, sn = slots.shape
    if rows != spec.rows_active:
        raise KernelInfeasible(
            f"slots grouped at {rows} rows but spec.rows_active="
            f"{spec.rows_active}; re-plan (slots cannot be regrouped)"
        )
    if g * rows < k:
        raise KernelInfeasible(
            f"slots cover K={g * rows} < input K={k}"
        )
    x = jnp.pad(x_codes.astype(jnp.float32), ((0, 0), (0, g * rows - k)))
    xg = x.reshape(m, g, rows).transpose(1, 0, 2)  # [G, M, rows]
    return jax.lax.dot_general(
        xg, slots, (((2,), (1,)), ((0,), (0,))),
        precision=_EXACT,
        preferred_element_type=jnp.float32,
    )


def _iter_slot_planes(
    combined, spec, ss
) -> Iterator[tuple[int, jax.Array]]:
    """Yield (plane index b, exact integer pMAC [G, M, N]) per plane."""
    b_total = spec.weight_bits
    inv = 1.0 / float(ss.stride)
    for s in range(ss.n_slots):
        cs = combined[..., s, :]
        lo = s * ss.per_slot
        for j in range(min(ss.per_slot, b_total - lo)):
            hi = jnp.floor(cs * inv)
            yield lo + j, cs - hi * float(ss.stride)
            cs = hi


def _plane_sign(b: int, weight_bits: int) -> float:
    """Two's-complement shift-add weight of plane b, as a Python float.

    Static (not a traced ``plane_signs`` element): the slot epilogue
    folds it into compile-time scalar multipliers.
    """
    s = float(1 << b)
    return -s if b == weight_bits - 1 else s


def _slot_geometry(slots, spec):
    ss = slot_spec(spec.rows_active, spec.act_bits, spec.weight_bits)
    if ss is None:
        raise KernelInfeasible(
            "spread slots infeasible at this operating point "
            f"(rows_active={spec.rows_active}, act_bits={spec.act_bits})"
        )
    sn = slots.shape[-1]
    if sn % ss.n_slots != 0:
        raise KernelInfeasible(
            f"slots last dim {sn} is not divisible by n_slots="
            f"{ss.n_slots}; operand packed for a different operating "
            "point"
        )
    return ss, sn // ss.n_slots


def cim_matmul_slots(
    x_codes: jax.Array,
    slots: jax.Array,
    cfg: CIMConfig,
) -> jax.Array:
    """P-8T per-plane transfer over spread-slot planes. [M,K] -> [M,N].

    ``slots`` is the plan's ``quant.spread_slots`` operand, grouped at
    ``cfg.rows_active``. Bit-exact vs :func:`cim_matmul_ref` for both
    adc modes; noiseless by definition. Also serves the cell-adc
    variant, whose noise-free SAR codes equal this transfer exactly.
    """
    # f32 group accumulation of dequantized plane codes stays exact up
    # to the contraction depths registered for this geometry.
    # bound(CIM601): G * 2**(weight_bits - 1) * threshold < 2**23 * adc_step
    ss, n = _slot_geometry(slots, cfg)
    g = slots.shape[0]
    m = x_codes.shape[0]
    c = _slot_dot(x_codes, slots, cfg).reshape(g, m, ss.n_slots, n)
    half = 0.5 if getattr(cfg, "adc_mode", "floor") == "nearest" else 0.0
    inv_step = 1.0 / float(cfg.adc_step)
    acc = jnp.zeros((g, m, n), jnp.float32)
    for b, pmac in _iter_slot_planes(c, cfg, ss):
        code = jnp.clip(
            jnp.floor(pmac * inv_step + half), 0, cfg.adc_codes - 1
        )
        acc = acc + code * (
            _plane_sign(b, cfg.weight_bits) * float(cfg.adc_step)
        )
    return jnp.sum(acc, axis=0)


def adder_tree_matmul_slots(
    x_codes: jax.Array,
    slots: jax.Array,
    cfg: CIMConfig,
) -> jax.Array:
    """Merged single-ADC transfer over spread-slot planes.

    Recovers the per-plane pMACs from the combined dot, folds them
    through the binary-weighted charge-domain adder (MSB negative) and
    applies the ONE merged conversion per (group, output) — bit-exact
    vs :func:`adder_tree_matmul_ref`.
    """
    from repro.core.variants import merged_quant  # noqa: PLC0415 - no cycle

    # Merged codes are summed over G groups in f32; the worst merged
    # code magnitude times depth must stay below the mantissa.
    # bound(CIM601): G * max(-code_min, code_max) < 2**24
    ss, n = _slot_geometry(slots, cfg)
    g = slots.shape[0]
    m = x_codes.shape[0]
    c = _slot_dot(x_codes, slots, cfg).reshape(g, m, ss.n_slots, n)
    merged = jnp.zeros((g, m, n), jnp.float32)
    for b, pmac in _iter_slot_planes(c, cfg, ss):
        merged = merged + pmac * _plane_sign(b, cfg.weight_bits)
    mq = merged_quant(cfg)
    half = 0.5 if getattr(cfg, "adc_mode", "floor") == "nearest" else 0.0
    code = jnp.clip(
        jnp.floor(merged / mq.step + half), mq.code_min, mq.code_max
    )
    return jnp.sum(code, axis=0) * mq.step
