"""A plain Qwen2-style decoder served through the P-8T macro.

Written from the architecture (arXiv:2407.10671 and the Hugging Face
Qwen2 modelling code): token embedding; per layer RMSNorm, attention
with grouped K/V heads, biases on the q/k/v projections and rotary
embeddings (rotate-half form), a residual add, RMSNorm, a SwiGLU MLP
and a residual add; a final RMSNorm and the tied embedding as LM head.
Every q/k/v/o/gate/up/down projection runs through ``macro.linear``.

Serving semantics that decide the macro's inputs:

* activations are held in the configuration's dtype ``adt`` (bfloat16)
  between operations; norms, rotary embeddings and the attention core
  compute in float32 and round back to ``adt``; the quantizer computes
  in ``adt``; matmuls outside the macro take JAX's default precision,
  as a bf16 model's do (on a TPU an f32 operand enters the MXU rounded
  to bf16: the attention probabilities, here);
* the macro turns a one-ulp change of a quantizer's input range into a
  different code for many elements, and its ADC floors make the output
  a coarse function of the codes, so two computations that differ by
  one rounding anywhere part after a layer; the reference therefore
  follows the serving computation's order of operations and reduction
  lengths: the prompt's attention over the prompt, a decode step's over
  the whole cache (masked);
* a projection's activation range is per call: over every prompt
  position of the batch in the prefill, over the batch's rows at each
  decode step;
* each RMSNorm is a program of its own, over the shapes the serving
  computation normalises: the prompt as one [B, S, d] call, each decode
  step as a [B, 1, d] call, the final norm at each served position as
  a [B, 1, d] call. The compiler sums the 896 squares in an order that
  depends on the shape and on the program around it (inside a larger
  program it may lay [B, T, d] out with T minor); the last bit of a
  few norms then moves, a few 4-bit codes change, and the layers part.

Teacher-forced on the served tokens, all decode steps of one layer are
computed together: a step's quantization group is its own position
across the batch, and its attention sees the keys of the prompt and of
the earlier steps, exactly what the cache held when it was served.
Layer by layer, so the reference fits beside nothing else on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.reference import macro

HIGHEST = jax.lax.Precision.HIGHEST
# Compile as written: every declared dtype honoured (no wider
# intermediates kept where the compiler would like to).
EXACT = {"xla_allow_excess_precision": False}


@functools.partial(jax.jit, static_argnames=("eps",), compiler_options=EXACT)
def rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def norm_as_served(x, scale, eps, s: int):
    """RMSNorm of x [B, T, d]: positions < s as one call, then each
    later position as a call of its own."""
    parts = [rmsnorm(x[:, :s], scale, eps)] if s else []
    if x.shape[1] > s:
        parts += [rmsnorm(xi, scale, eps)
                  for xi in jnp.split(x[:, s:], x.shape[1] - s, axis=1)]
    return jnp.concatenate(parts, axis=1)


def rope(x, positions, theta):
    """x [B, T, H, hd]; positions [T]."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * freqs  # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def cim_linear(x, p, s: int, m: macro.Macro):
    """x [B, T, K] (adt) -> [B, T, N] (adt) through the macro, + bias:
    the prompt positions of all rows as one call, then each decode step
    (one position of all rows) as a call of its own."""
    b, t, k = x.shape
    w = p["w"]  # macro.Weights, stored once per layer (store_layer)

    def call(x2):
        y = macro.linear(x2, w, m).astype(x.dtype)
        if "b" in p:
            y = y + p["b"].astype(y.dtype)
        return y

    out = [call(x[:, :s].reshape(b * s, k)).reshape(b, s, -1)]
    if t > s:
        out.append(jnp.swapaxes(jax.lax.map(call, jnp.swapaxes(x[:, s:], 0, 1)),
                                0, 1))
    return jnp.concatenate(out, axis=1)


def attention(q, k, v, mask):
    """GQA core in float32 as a bf16 model computes it: q [B, S, H, hd],
    k/v [B, T, KVH, hd], mask [S, T] (True where a query sees a key)."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, s, kvh, h // kvh, hd).astype(jnp.float32)
    scores = jnp.einsum("bsgrh,btgh->bgrst", qg,
                        k.astype(jnp.float32)) * hd ** -0.5
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrst,btgh->bsgrh", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h * hd).astype(q.dtype)


def self_attention(q, k, v, s: int, cache_len: int):
    """Prompt positions attend causally among themselves; each decode
    step's query attends, over a cache of ``cache_len`` slots, to the
    keys of the prompt and of the steps up to its own."""
    t = q.shape[1]
    out = [attention(q[:, :s], k[:, :s], v[:, :s],
                     jnp.arange(s)[None, :] <= jnp.arange(s)[:, None])]
    if t > s:
        pad = ((0, 0), (0, cache_len - t), (0, 0), (0, 0))
        kc, vc = jnp.pad(k, pad), jnp.pad(v, pad)

        def step(args):  # one decode step's query, as served: [B, 1, ...]
            qi, p = args
            mask = (jnp.arange(cache_len) <= p)[None, :]
            return attention(qi[:, None], kc, vc, mask)[:, 0]

        steps = jax.lax.map(step, (jnp.swapaxes(q[:, s:], 0, 1),
                                   jnp.arange(s, t)))
        out.append(jnp.swapaxes(steps, 0, 1))
    return jnp.concatenate(out, axis=1)


@functools.partial(jax.jit, static_argnames=("m",))
def store_layer(units, li, m: macro.Macro):
    """Layer ``li`` of the stacked ``units`` with every projection's
    weight written into the macro (``macro.store``), as the serving plan
    holds it before any input arrives."""
    lp = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False),
        units)
    for group in ("attn", "mlp"):
        for name, proj in lp[group].items():
            lp[group][name] = dict(proj, w=macro.store(proj["w"], m))
    return lp


@functools.partial(jax.jit, static_argnames=("s", "cache_len", "shape", "m"),
                   compiler_options=EXACT)
def attention_block(a, h, x, *, s: int, cache_len: int, shape: tuple,
                    m: macro.Macro):
    """x + attention over the normed h [B, T, d]: prompt positions < s,
    then one decode step per later position. ``shape``: the sorted items
    of the sizes dict (hashable, static)."""
    shape = dict(shape)
    b, t, _ = h.shape
    hd, theta = shape["head_dim"], shape["rope_theta"]
    pos = jnp.arange(t, dtype=jnp.int32)
    q = cim_linear(h, a["wq"], s, m).reshape(b, t, -1, hd)
    k = cim_linear(h, a["wk"], s, m).reshape(b, t, -1, hd)
    v = cim_linear(h, a["wv"], s, m).reshape(b, t, -1, hd)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    return x + cim_linear(self_attention(q, k, v, s, cache_len), a["wo"],
                          s, m)


@functools.partial(jax.jit, static_argnames=("s", "m"),
                   compiler_options=EXACT)
def mlp_block(mlp, h, x, *, s: int, m: macro.Macro):
    """x + the SwiGLU MLP of the normed h."""
    g = cim_linear(h, mlp["gate"], s, m)
    u = cim_linear(h, mlp["up"], s, m)
    return x + cim_linear(jax.nn.silu(g) * u, mlp["down"], s, m)


def layer(lp, x, *, s: int, cache_len: int, shape: tuple, m: macro.Macro):
    """One decoder layer (``store_layer`` output) over x [B, T, d]."""
    eps = dict(shape)["eps"]
    h = norm_as_served(x, lp["norm1"]["scale"], eps, s)
    x = attention_block(lp["attn"], h, x, s=s, cache_len=cache_len,
                        shape=shape, m=m)
    h = norm_as_served(x, lp["norm2"]["scale"], eps, s)
    return mlp_block(lp["mlp"], h, x, s=s, m=m)


def final_hidden(params, prompts, fed, shape: dict, m: macro.Macro, adt,
                 cache_len: int):
    """Normed final hidden states at every position that produced a
    served token: the last prompt position, then each fed token.

    prompts [B, S] and fed [B, D] (the served tokens fed back, all but
    the last) -> [B, 1 + D, d] in ``adt``. ``cache_len``: the serving
    cache's length, over which a decode step's attention runs.
    """
    s = prompts.shape[1]
    tokens = jnp.concatenate([prompts, fed], axis=1)
    x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(adt)
    units = params["units"]["layer_00"]
    frozen = tuple(sorted(shape.items()))
    for li in range(shape["layers"]):
        lp = store_layer(units, jnp.int32(li), m)
        x = layer(lp, x, s=s, cache_len=cache_len, shape=frozen, m=m)
    return norm_as_served(x[:, s - 1:], params["final_norm"]["scale"],
                          shape["eps"], 0)



@functools.partial(jax.jit, static_argnames=("vocab",))
def _logits(h, table, vocab: int):
    return jnp.einsum("btd,vd->btv", h.astype(jnp.float32),
                      table[:vocab].astype(h.dtype).astype(jnp.float32),
                      precision=HIGHEST)


def token_gaps(h, table, vocab: int, pick=None, block: int = 8):
    """Per position, by how much the reference logit of a token lies
    below the reference's best. ``pick`` [B, P] gives the tokens; None
    returns, per position, the logits' argmax of ``h`` itself.

    Logits are f32 over the vocabulary, from the adt hidden states and
    the adt-rounded tied embedding, ``block`` positions at a time.
    Returns a list of [B, block] arrays, or the argmax tokens.
    """
    out = []
    for lo in range(0, h.shape[1], block):
        lg = _logits(h[:, lo:lo + block], table, vocab)
        if pick is None:
            out.append(jnp.argmax(lg, axis=-1))
        else:
            sel = jnp.take_along_axis(lg, pick[:, lo:lo + block, None],
                                      axis=-1)[..., 0]
            out.append(jnp.max(lg, axis=-1) - sel)
    return jnp.concatenate(out, axis=1)
