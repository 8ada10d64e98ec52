"""GQA attention with causal/local masking, KV caches and cross-attention.

Weight projections route through the CIM execution layer (they are
weight-stationary -- DESIGN.md Sec. 5); the attention core itself
(QK^T, softmax, PV) is activation x activation and stays digital.

Cache layouts:
  full cache  : k/v [B, C, KVH, hd], written at absolute position.
  ring cache  : C == window; slot = pos % window (local layers; RoPE is
                applied at write time with absolute positions so relative
                offsets survive the ring indexing).
Decode is one query token against the cache; prefill writes the cache in
bulk and runs the masked quadratic core.

Device scopes (``jax.named_scope``, metadata only): ``attn.core`` on the
QK/softmax/PV cores, ``attn.cache`` on the K/V cache writes.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import CIMPolicy, ModelConfig
from repro.distributed.sharding import constrain_query
from repro.models import common
from repro.models.common import ParamSpec


class KVCache(NamedTuple):
    k: jax.Array  # [B, C, KVH, hd]
    v: jax.Array  # [B, C, KVH, hd]


def attn_spec(cfg: ModelConfig, *, cross: bool = False) -> dict:
    d = cfg.d_model
    spec = {
        "wq": common.linear_spec(d, cfg.q_dim, "embed", "heads",
                                 bias=cfg.qkv_bias),
        "wk": common.linear_spec(d, cfg.kv_dim, "embed", "kv_heads",
                                 bias=cfg.qkv_bias),
        "wv": common.linear_spec(d, cfg.kv_dim, "embed", "kv_heads",
                                 bias=cfg.qkv_bias),
        "wo": common.linear_spec(cfg.q_dim, d, "heads", "embed"),
    }
    if cross:
        # Cross-attention never uses RoPE; same projection shapes.
        pass
    return spec


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, *, window: int = 0,
    dtype=jnp.float32,
) -> KVCache:
    c = min(window, max_len) if window else max_len
    shape = (batch, c, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def _project_qkv(params, x, cfg: ModelConfig, policy: CIMPolicy | None,
                 key=None):
    en = policy.apply_to_attn_proj if policy else False
    ks = jax.random.split(key, 3) if key is not None else (None,) * 3
    b, s, _ = x.shape
    q = common.linear_apply(params["wq"], x, policy, cim_enabled=en,
                            key=ks[0])
    k = common.linear_apply(params["wk"], x, policy, cim_enabled=en,
                            key=ks[1])
    v = common.linear_apply(params["wv"], x, policy, cim_enabled=en,
                            key=ks[2])
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


@jax.named_scope("attn.core")
def _gqa_core(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,  # [B, T, KVH, hd]
    v: jax.Array,  # [B, T, KVH, hd]
    mask: jax.Array | None,  # broadcastable to [B, G, R, S, T], bool
) -> jax.Array:
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    rep = h // kvh
    qg = q.reshape(b, s, kvh, rep, hd)
    scale = hd**-0.5
    scores = jnp.einsum(
        "bsgrh,btgh->bgrst", qg.astype(jnp.float32),
        k.astype(jnp.float32),
    ) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrst,btgh->bsgrh", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h, hd).astype(q.dtype)


@jax.named_scope("attn.core")
def _flash_core(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,  # [B, T, KVH, hd]
    v: jax.Array,  # [B, T, KVH, hd]
    *,
    q_positions: jax.Array,  # [S] absolute positions of the queries
    window: int = 0,
    block: int = 1024,
) -> jax.Array:
    """Online-softmax (flash) attention: lax.scan over KV blocks.

    Never materializes the [S, T] score matrix -- peak temp is one
    [B, G, R, S, block] tile plus the (m, l, acc) carry. This is what
    makes 32k-prefill fit HBM (yi-34b: 59 GiB -> ~2 GiB temp); it is
    bit-equivalent to _gqa_core up to f32 summation order (tested).
    Causality/window are enforced from absolute positions, so it works
    for both training (q over the whole seq) and chunked prefill.
    """
    b, s, h, hd = q.shape
    t = k.shape[1]
    kvh = k.shape[2]
    rep = h // kvh
    scale = hd**-0.5
    # q/k/v stream in their storage dtype (full f32 staging copies cost
    # 2 GiB each at 32k); per-block score math accumulates in f32 via
    # preferred_element_type.
    qg = (q.reshape(b, s, kvh, rep, hd) * jnp.asarray(scale, q.dtype))

    pad = (-t) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = (t + pad) // block

    def tb(a):  # [B, T, KVH, hd] -> [nb, B, block, KVH, hd]
        return a.reshape(b, nb, block, kvh, hd).swapaxes(0, 1)

    kb, vb = tb(k), tb(v)

    m0 = jnp.full((b, kvh, rep, s), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kvh, rep, s), jnp.float32)
    a0 = jnp.zeros((b, kvh, rep, s, hd), jnp.float32)

    def body(carry, inp):
        m, l, acc, bi = carry
        kblk, vblk = inp
        kv_pos = bi * block + jnp.arange(block)
        sblk = jnp.einsum("bsgrh,btgh->bgrst", qg,
                          kblk.astype(qg.dtype),
                          preferred_element_type=jnp.float32)
        ok = (kv_pos[None, :] <= q_positions[:, None]) & (
            kv_pos[None, :] < t
        )
        if window:
            ok &= kv_pos[None, :] > q_positions[:, None] - window
        sblk = jnp.where(ok[None, None, None], sblk, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(sblk, axis=-1))
        # exp(-inf - -inf) guards: rows with no valid key stay empty.
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(sblk - safe_m[..., None])
        p = jnp.where(ok[None, None, None], p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bgrst,btgh->bgrsh", p, vblk.astype(jnp.float32)
        )
        return (m_new, l, acc, bi + 1), None

    (m, l, acc, _), _ = jax.lax.scan(
        body, (m0, l0, a0, jnp.asarray(0, jnp.int32)), (kb, vb)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B,G,R,S,hd]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, hd)
    return out.astype(q.dtype)


# Sequence length above which the quadratic core switches to the
# flash formulation (the [S, T] score tensor stops fitting HBM).
FLASH_THRESHOLD = 4096


def _self_attention_core(q, k, v, *, positions, window, s):
    """Dispatch: materialized masked core for seqs up to 4k (cheapest
    under remat -- the flash scan's saved per-block residuals cost as
    much as the full score matrix at 4k and regressed gemma3 train_4k
    by +2 GiB), flash strictly above (32k prefill: yi-34b 68->14.7 GiB
    measured)."""
    if s > FLASH_THRESHOLD:
        return _flash_core(q, k, v, q_positions=positions,
                           window=window)
    mask = causal_mask(s, s, window=window)[None, None, None]
    return _gqa_core(q, k, v, mask)


def causal_mask(s: int, t: int, *, offset: int = 0,
                window: int = 0) -> jax.Array:
    """[S, T] bool; query i attends key j iff j <= i+offset (and within
    the sliding window when window > 0)."""
    qi = jnp.arange(s)[:, None] + offset
    kj = jnp.arange(t)[None, :]
    m = kj <= qi
    if window:
        m &= kj > qi - window
    return m


def attend_full(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,  # [B, S]
    window: int = 0,
    policy: CIMPolicy | None = None,
    key: jax.Array | None = None,
) -> jax.Array:
    """Training / prefill self-attention (no cache returned)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, x, cfg, policy, key)
    q = constrain_query(common.apply_rope(q, positions, cfg.rope_theta))
    k = common.apply_rope(k, positions, cfg.rope_theta)
    out = _self_attention_core(q, k, v, positions=positions[0],
                               window=window, s=s)
    en = policy.apply_to_attn_proj if policy else False
    return common.linear_apply(
        params["wo"], out.reshape(b, s, cfg.q_dim), policy,
        cim_enabled=en, key=key,
    )


def prefill_cache(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    cache: KVCache,
    *,
    positions: jax.Array,
    window: int = 0,
    policy: CIMPolicy | None = None,
    key: jax.Array | None = None,
) -> tuple[jax.Array, KVCache]:
    """Prefill: run full attention AND populate the cache."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, x, cfg, policy, key)
    q = constrain_query(common.apply_rope(q, positions, cfg.rope_theta))
    k = common.apply_rope(k, positions, cfg.rope_theta)
    c = cache.k.shape[1]
    with jax.named_scope("attn.cache"):
        kc = k.astype(cache.k.dtype)  # cache may be fp8 (storage dtype)
        vc = v.astype(cache.v.dtype)
        if window and c == window:
            # Keep the last `window` tokens, slot = pos % window.
            take = min(s, window)
            idx = (positions[:, -take:] % window).astype(jnp.int32)
            bidx = jnp.arange(b)[:, None]
            new_k = cache.k.at[bidx, idx].set(kc[:, -take:])
            new_v = cache.v.at[bidx, idx].set(vc[:, -take:])
        else:
            new_k = jax.lax.dynamic_update_slice(
                cache.k, kc, (0, 0, 0, 0)
            )
            new_v = jax.lax.dynamic_update_slice(
                cache.v, vc, (0, 0, 0, 0)
            )
    out = _self_attention_core(q, k, v, positions=positions[0],
                               window=window, s=s)
    en = policy.apply_to_attn_proj if policy else False
    y = common.linear_apply(
        params["wo"], out.reshape(b, s, cfg.q_dim), policy,
        cim_enabled=en, key=key,
    )
    return y, KVCache(new_k, new_v)


def decode_step(
    params: dict,
    x: jax.Array,  # [B, 1, D]
    cfg: ModelConfig,
    cache: KVCache,
    pos: jax.Array,  # scalar int32: position of the new token
    *,
    window: int = 0,
    policy: CIMPolicy | None = None,
    key: jax.Array | None = None,
) -> tuple[jax.Array, KVCache]:
    """One decode step against the cache (full or ring)."""
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    q, k, v = _project_qkv(params, x, cfg, policy, key)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)

    c = cache.k.shape[1]
    ring = window and c == window
    with jax.named_scope("attn.cache"):
        kc = k.astype(cache.k.dtype)  # cache may be fp8 (storage dtype)
        vc = v.astype(cache.v.dtype)
        slot = (pos % window if ring else pos).astype(jnp.int32)
        new_k = jax.lax.dynamic_update_slice(cache.k, kc, (0, slot, 0, 0))
        new_v = jax.lax.dynamic_update_slice(cache.v, vc, (0, slot, 0, 0))
    if ring:
        # Slots 0..pos valid until the ring wraps; afterwards every slot
        # holds one of the last `window` tokens.
        valid = (jnp.arange(c)[None, :] < pos + 1) | (pos + 1 >= c)
        mask = valid[None, None, None, :]
    else:
        mask = (jnp.arange(c) <= pos)[None, None, None, None, :]

    out = _gqa_core(q, new_k, new_v, mask)
    en = policy.apply_to_attn_proj if policy else False
    y = common.linear_apply(
        params["wo"], out.reshape(b, 1, cfg.q_dim), policy,
        cim_enabled=en, key=key,
    )
    return y, KVCache(new_k, new_v)


def cross_attend(
    params: dict,
    x: jax.Array,  # [B, S, D] decoder states
    memory_kv: tuple[jax.Array, jax.Array],  # precomputed enc K/V
    cfg: ModelConfig,
    *,
    policy: CIMPolicy | None = None,
    key: jax.Array | None = None,
) -> jax.Array:
    """Encoder-decoder cross attention with precomputed memory K/V."""
    b, s, _ = x.shape
    en = policy.apply_to_attn_proj if policy else False
    q = common.linear_apply(params["wq"], x, policy, cim_enabled=en,
                            key=key)
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k, v = memory_kv
    out = _gqa_core(q, k, v, None)
    return common.linear_apply(
        params["wo"], out.reshape(b, s, cfg.q_dim), policy,
        cim_enabled=en, key=key,
    )


def encode_memory_kv(
    params: dict, memory: jax.Array, cfg: ModelConfig,
    *, policy: CIMPolicy | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Precompute cross-attention K/V from encoder output."""
    b, t, _ = memory.shape
    en = policy.apply_to_attn_proj if policy else False
    k = common.linear_apply(params["wk"], memory, policy, cim_enabled=en)
    v = common.linear_apply(params["wv"], memory, policy, cim_enabled=en)
    return (
        k.reshape(b, t, cfg.n_kv_heads, cfg.head_dim),
        v.reshape(b, t, cfg.n_kv_heads, cfg.head_dim),
    )
