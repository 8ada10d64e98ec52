"""The P-8T macro matmul and its digital periphery, written plainly.

Operating point (paper Sec. III-IV): unsigned ``act_bits`` activation
codes drive ``rows_active``-row groups of one accumulation bit line;
signed ``weight_bits`` weights are stored as two's-complement bit
planes. Per row group g and plane b the partial MAC
pMAC = sum_r x[r] * bit_b(w[r]) is read by a flash ADC with step
``threshold / 2**adc_bits``, threshold = (1 - cutoff) * 2**q_full,
code = clip(floor(pMAC / step), 0, 2**adc_bits - 1), and the codes are
shift-added (the MSB plane negative). Activations are quantized per
tensor (one range over every row of the call), weights per output
column over K; the zero point is corrected digitally with the column
sums of the weight codes.

Integer parts run as int8 x int8 -> int32 contractions and int32
arithmetic: exact by construction.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Macro:
    rows_active: int = 16
    act_bits: int = 4
    weight_bits: int = 8
    adc_bits: int = 4
    cutoff: float = 0.5

    @classmethod
    def from_config(cls, c: dict) -> "Macro":
        return cls(**c)

    @property
    def act_max(self) -> int:
        return (1 << self.act_bits) - 1

    @property
    def adc_step(self) -> int:
        q_full = max(1, math.ceil(math.log2(self.rows_active * self.act_max
                                            + 1)))
        threshold = max(1, int(round((1.0 - self.cutoff) * (1 << q_full))))
        step = threshold / (1 << self.adc_bits)
        if not float(step).is_integer():
            raise ValueError(f"this reference needs an integer ADC step, "
                             f"got {step}")
        return int(step)


def quantize_weights(w: jax.Array, weight_bits: int):
    """Symmetric per-output-column codes of w [K, N] (range over K).

    The scale is the quotient amax / qmax, rounded once, as the serving
    plan computes it. Under jit XLA's CPU backend turns a division by
    the constant qmax into a multiplication by its rounded reciprocal,
    which moves about one column scale in twenty by an ulp and, now and
    then, a weight code; the barrier keeps qmax out of sight of that
    rewrite."""
    qmax = jax.lax.optimization_barrier(
        jnp.float32((1 << (weight_bits - 1)) - 1))
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    codes = jnp.clip(jnp.round(w / scale), -qmax - 1, qmax).astype(jnp.int32)
    return codes, scale


def quantize_acts(x: jax.Array, lo: jax.Array, hi: jax.Array, act_bits: int,
                  symmetric: bool):
    """Codes, scale and zero point of x for the range [lo, hi], all in
    x's dtype. ``lo``/``hi`` broadcast against x: one range per
    quantization group (a call's whole input)."""
    qmax = (1 << act_bits) - 1
    if symmetric:
        scale = jnp.maximum(hi, 1e-8) / qmax
        zp = jnp.zeros_like(scale, dtype=jnp.int32)
        codes = jnp.clip(jnp.round(x / scale), 0, qmax).astype(jnp.int32)
        return codes, scale, zp
    hi = jnp.maximum(hi, lo + 1e-8)
    scale = (hi - lo) / qmax
    zp = jnp.clip(jnp.round(-lo / scale), 0, qmax).astype(jnp.int32)
    codes = jnp.clip(jnp.round(x / scale) + zp, 0, qmax).astype(jnp.int32)
    return codes, scale, zp


class Weights(NamedTuple):
    """A weight matrix as the macro holds it: two's-complement bit planes
    [B, G, rows, N] (int8 0/1, K zero-padded to whole groups), the
    column sums of the codes, and the per-column dequant scale."""

    planes: jax.Array
    colsum: jax.Array
    scale: jax.Array


def store(w: jax.Array, m: Macro) -> Weights:
    codes, scale = quantize_weights(w, m.weight_bits)
    k, n = codes.shape
    g = -(-k // m.rows_active)
    u = jnp.pad(jnp.bitwise_and(codes, (1 << m.weight_bits) - 1),
                ((0, g * m.rows_active - k), (0, 0)))
    u = u.reshape(g, m.rows_active, n)
    planes = jnp.stack([jnp.bitwise_and(jnp.right_shift(u, b), 1)
                        for b in range(m.weight_bits)]).astype(jnp.int8)
    colsum = jnp.sum(codes, axis=0, keepdims=True).astype(jnp.float32)
    return Weights(planes, colsum, scale)


def _block_rows(g: int, n: int, target: int = 1 << 26) -> int:
    """Rows per block so that one plane's [G, rows, N] pMACs stay near
    ``target`` elements."""
    rows = max(8, target // max(1, g * n))
    return 1 << (rows.bit_length() - 1)


def macro_int(codes: jax.Array, planes: jax.Array, m: Macro) -> jax.Array:
    """Sum over groups and planes of the shift-added ADC codes, in pMAC
    units: [M, K] activation codes x stored planes -> [M, N] f32."""
    mm, k = codes.shape
    _, g, rows, n = planes.shape
    step = m.adc_step
    signs = [(1 << b) * (-1 if b == m.weight_bits - 1 else 1)
             for b in range(m.weight_bits)]
    br = min(_block_rows(g, n), 1 << max(0, (mm - 1).bit_length()))
    nb = -(-mm // br)
    x = jnp.pad(codes, ((0, nb * br - mm), (0, g * rows - k)))
    x = x.astype(jnp.int8).reshape(nb, br, g, rows)

    def block(xb):  # [br, G, rows] -> [br, N]
        acc = jnp.zeros((br, n), jnp.int32)
        for b, sign in enumerate(signs):
            pmac = jnp.einsum("mgr,grn->gmn", xb, planes[b],
                              preferred_element_type=jnp.int32)
            code = jnp.clip(pmac // step, 0, (1 << m.adc_bits) - 1)
            acc = acc + sign * step * jnp.sum(code, axis=0)
        return acc

    y = jax.lax.map(block, x).reshape(nb * br, n)[:mm]
    return y.astype(jnp.float32)


def linear(x2: jax.Array, w: Weights, m: Macro, *,
           symmetric: bool = False) -> jax.Array:
    """y ~= x2 @ W through the macro for one call: x2 [M, K] in the
    activation dtype, quantized over its own range (the whole call).
    Returns f32 [M, N] before any cast or bias."""
    lo = jnp.min(x2, keepdims=True)
    hi = jnp.max(x2, keepdims=True)
    codes, scale, zp = quantize_acts(x2, lo, hi, m.act_bits, symmetric)
    y = macro_int(codes, w.planes, m) - zp.astype(jnp.float32) * w.colsum
    return y * scale.astype(jnp.float32) * w.scale
