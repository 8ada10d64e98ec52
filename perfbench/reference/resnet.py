"""A plain CIFAR ResNet (He et al. 2016, Sec. 4.2) served through the
P-8T macro, the paper's own evaluation network.

3x3 stem conv (digital), BatchNorm with running statistics, ReLU; basic
blocks of two 3x3 convs with BatchNorm, a stride-2 first conv and a
1x1 projection shortcut where the width changes; global average pool;
a digital fc layer. Every block conv runs as im2col through
``macro.linear`` with unsigned (post-ReLU) activation codes, one range
per call: the whole batch's im2col matrix.

Everything computes in ``dt`` (float32 as configured) with matmuls and
convolutions at JAX's default precision, as the configuration states
(on a TPU: operands enter the MXU rounded to bfloat16, f32 sums; the
im2col patch extraction is such a convolution). The macro's 4-bit
quantizer turns one rounding more or less into another code, so the
reference computes each conv as one call over the batch's whole im2col
matrix, in the serving computation's order of operations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.reference import macro

DN = ("NHWC", "HWIO", "NHWC")


def _bn(p, s, x):
    y = (x - s["mean"].astype(x.dtype)) * jax.lax.rsqrt(
        s["var"].astype(x.dtype) + 1e-5)
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def _cim_conv(w, x, stride: int, m: macro.Macro):
    kh, kw, cin, cout = w.shape
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (stride, stride), "SAME",
        dimension_numbers=DN)  # features ordered [cin, kh, kw]
    b, ho, wo, pf = patches.shape
    x2 = patches.reshape(-1, pf)
    wmat = jnp.transpose(w, (2, 0, 1, 3)).reshape(pf, cout)
    y = macro.linear(x2, macro.store(wmat, m), m, symmetric=True)
    return y.astype(x.dtype).reshape(b, ho, wo, cout)


@functools.partial(jax.jit, static_argnames=("widths", "blocks", "m", "dt"))
def forward(params, bn, images, *, widths, blocks, m: macro.Macro, dt):
    x = images.astype(dt)
    h = jax.lax.conv_general_dilated(
        x, params["stem"].astype(dt), (1, 1), "SAME", dimension_numbers=DN)
    h = jax.nn.relu(_bn(params["bn_stem"], bn["bn_stem"], h))
    for si, _ in enumerate(widths):
        for bi in range(blocks):
            name = f"s{si}b{bi}"
            p, s = params[name], bn[name]
            stride = 2 if (bi == 0 and si > 0) else 1
            r = jax.nn.relu(_bn(p["bn1"], s["bn1"],
                                _cim_conv(p["conv1"], h, stride, m)))
            r = _bn(p["bn2"], s["bn2"], _cim_conv(p["conv2"], r, 1, m))
            if "proj" in p:
                sc = _bn(p["bn_proj"], s["bn_proj"],
                         _cim_conv(p["proj"], h, stride, m))
            else:
                sc = h
            h = jax.nn.relu(r + sc)
    h = jnp.mean(h, axis=(1, 2))
    fc = params["fc"]
    return (jnp.dot(h, fc["w"].astype(dt))
            + fc["b"].astype(dt)).astype(jnp.float32)
