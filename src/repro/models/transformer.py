"""Unified LM: one config-driven stack covering all assigned archs.

A model is a repeating *pattern unit* of layers (gemma3: 5 local + 1
global; jamba: 1 attn + 7 mamba with MoE on every 2nd layer; rwkv: one
rwkv layer; dense: one attn layer). Units with identical structure are
stacked and scanned (small HLO for 60-72 layer archs + FSDP overlap);
the non-multiple remainder runs unrolled as a tail.

Entry points:
  init(key, cfg)                      -> params
  model_axes(cfg)                     -> logical-axis pytree (sharding)
  forward_train(params, batch, cfg)   -> logits, aux
  loss_fn(params, batch, cfg, key)    -> scalar loss, metrics
  init_caches / prefill / decode_step -> serving path
Encoder-decoder (whisper) adds encode() and uses cross-attention in the
decoder; VLM/audio frontends are embedding stubs per the assignment.

Device scopes (``jax.named_scope``, metadata only): ``lm.norm`` on every
RMSNorm, ``lm.head`` on the final norm and the logits.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import CIMPolicy, ModelConfig
from repro.distributed.sharding import constrain
from repro.models import attention, common, mamba, moe, rwkv
from repro.models.attention import KVCache
from repro.models.common import ParamSpec

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Spec construction
# ---------------------------------------------------------------------------


def _layer_spec(cfg: ModelConfig, layer_idx: int, *, cross: bool = False
                ) -> dict:
    kind = cfg.layer_kind(layer_idx)
    spec: dict = {"norm1": common.rmsnorm_spec(cfg.d_model)}
    if kind in ("attn", "attn_local"):
        spec["attn"] = attention.attn_spec(cfg)
    elif kind == "mamba":
        spec["mamba"] = mamba.mamba_spec(cfg)
    elif kind == "rwkv":
        spec["tm"] = rwkv.rwkv_spec(cfg)
    else:  # pragma: no cover
        raise ValueError(kind)
    if cross:
        spec["norm_x"] = common.rmsnorm_spec(cfg.d_model)
        spec["xattn"] = attention.attn_spec(cfg, cross=True)
    spec["norm2"] = common.rmsnorm_spec(cfg.d_model)
    if kind == "rwkv":
        spec["cm"] = rwkv.channelmix_spec(cfg)
    elif cfg.layer_uses_moe(layer_idx):
        spec["moe"] = moe.moe_spec(cfg)
    else:
        spec["mlp"] = common.mlp_spec(cfg.d_model, cfg.d_ff, cfg.mlp_act)
    return spec


def _stack_spec(spec: Any, n: int) -> Any:
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init,
                            s.dtype),
        spec,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def _unit_split(cfg: ModelConfig) -> tuple[int, int, int]:
    """(pattern_len, n_scan_units, n_tail_layers)."""
    p = cfg.pattern_len
    if not cfg.scan_layers:
        return p, 0, cfg.n_layers
    n_units = cfg.n_layers // p
    return p, n_units, cfg.n_layers - n_units * p


def model_spec(cfg: ModelConfig) -> dict:
    p, n_units, n_tail = _unit_split(cfg)
    cross = cfg.is_encoder_decoder
    spec: dict = {
        "embed": common.embedding_spec(cfg.padded_vocab, cfg.d_model),
        "final_norm": common.rmsnorm_spec(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = common.linear_spec(
            cfg.d_model, cfg.padded_vocab, "embed", "vocab"
        )
    if n_units:
        unit = {f"layer_{j:02d}": _layer_spec(cfg, j, cross=cross)
                for j in range(p)}
        spec["units"] = _stack_spec(unit, n_units)
    for t in range(n_tail):
        li = n_units * p + t
        spec[f"tail_{t:02d}"] = _layer_spec(cfg, li, cross=cross)
    if cfg.is_encoder_decoder:
        enc_cfg = cfg.replace(
            is_encoder_decoder=False,
            layer_pattern=("attn",),
            moe=None,
        )
        spec["encoder"] = {
            f"enc_{j:02d}": _layer_spec(enc_cfg, j)
            for j in range(cfg.n_encoder_layers)
        }
        spec["enc_norm"] = common.rmsnorm_spec(cfg.d_model)
    if cfg.learned_pos_emb:
        spec["pos_emb"] = ParamSpec(
            (cfg.max_seq_len, cfg.d_model), (None, "embed"), "normal:0.01"
        )
    return spec


def model_axes(cfg: ModelConfig) -> Any:
    return common.logical_axes(model_spec(cfg))


def _apply_special_inits(params: Params, cfg: ModelConfig) -> Params:
    """S4D-real init for every mamba a_log leaf (stacked or not)."""
    if cfg.mamba is None:
        return params
    d_state = cfg.mamba.d_state
    base = jnp.log(jnp.arange(1, d_state + 1, dtype=jnp.float32))

    def fix(path, leaf):
        if path and getattr(path[-1], "key", None) == "a_log":
            return jnp.broadcast_to(base, leaf.shape).astype(leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


def init(key: jax.Array, cfg: ModelConfig) -> Params:
    params = common.init_params(key, model_spec(cfg))
    params = _apply_special_inits(params, cfg)
    dtype = jnp.dtype(cfg.param_dtype)
    return jax.tree.map(lambda a: a.astype(dtype), params)


# ---------------------------------------------------------------------------
# Layer application (train / prefill path)
# ---------------------------------------------------------------------------


class LayerAux(NamedTuple):
    moe_aux: jax.Array


def _norm(p, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    with jax.named_scope("lm.norm"):
        return common.rmsnorm_apply(p, x, cfg.norm_eps)


def _layer_apply(
    lp: Params,
    x: jax.Array,
    cfg: ModelConfig,
    layer_idx: int,
    *,
    positions: jax.Array,
    policy: CIMPolicy | None,
    key: jax.Array | None,
    memory_kv=None,
) -> tuple[jax.Array, jax.Array]:
    kind = cfg.layer_kind(layer_idx)
    h = _norm(lp["norm1"], x, cfg)
    if kind in ("attn", "attn_local"):
        window = cfg.window_size if kind == "attn_local" else 0
        a = attention.attend_full(
            lp["attn"], h, cfg, positions=positions, window=window,
            policy=policy, key=key,
        )
    elif kind == "mamba":
        a = mamba.mamba_apply(lp["mamba"], h, cfg, policy=policy, key=key)
    else:  # rwkv
        a, _, _ = rwkv.timemix_apply(lp["tm"], h, cfg, policy=policy,
                                     key=key)
    x = x + a.astype(x.dtype)

    if memory_kv is not None and "xattn" in lp:
        hx = _norm(lp["norm_x"], x, cfg)
        x = x + attention.cross_attend(lp["xattn"], hx, memory_kv, cfg,
                                       policy=policy, key=key)

    h = _norm(lp["norm2"], x, cfg)
    aux = jnp.zeros((), jnp.float32)
    if kind == "rwkv":
        m, _ = rwkv.channelmix_apply(lp["cm"], h, cfg, policy=policy,
                                     key=key)
    elif "moe" in lp:
        m, metrics = moe.moe_apply(lp["moe"], h, cfg, policy=policy,
                                   key=key)
        aux = metrics.aux_loss
    else:
        m = common.mlp_apply(lp["mlp"], h, cfg.mlp_act, policy, key=key)
    return x + m.astype(x.dtype), aux


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    # 'full' and 'layer' both checkpoint the unit body; 'layer'
    # additionally checkpoints each layer inside it (nested remat) so
    # the backward live set is one LAYER, not one pattern unit --
    # jamba's unit is 8 layers (1 attn + 7 mamba + 4 MoE FFNs) and a
    # unit-granular live set blows past HBM at d_model 8192.
    return jax.checkpoint(fn)


def _embed_inputs(params, batch, cfg: ModelConfig):
    """Token (+ stub-frontend) embedding. Returns (x, positions)."""
    tokens = batch["tokens"]
    x = common.embedding_apply(params["embed"], tokens)
    x = x.astype(jnp.dtype(cfg.activation_dtype))
    if cfg.frontend and "frontend_embeds" in batch:
        # VLM stub: precomputed patch embeddings prepended to the text.
        fe = batch["frontend_embeds"].astype(x.dtype)
        x = jnp.concatenate([fe, x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.learned_pos_emb:
        x = x + params["pos_emb"][:s][None].astype(x.dtype)
    x = constrain(x, ("act_batch", "act_seq", "act_embed"))
    return x, positions


def _logits(params, x, cfg: ModelConfig, policy: CIMPolicy | None):
    with jax.named_scope("lm.head"):
        h = _norm(params["final_norm"], x, cfg)
        if cfg.tie_embeddings:
            table = params["embed"]["table"].astype(h.dtype)
            logits = jnp.einsum("bsd,vd->bsv", h, table)
        else:
            en = policy.apply_to_logits if policy else False
            logits = common.linear_apply(params["lm_head"], h, policy,
                                         cim_enabled=en)
        if cfg.padded_vocab != cfg.vocab_size:
            # Vocab-pad columns never win argmax nor enter the softmax
            # mass.
            pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            logits = jnp.where(pad_mask, jnp.asarray(-1e30, logits.dtype),
                               logits)
        return constrain(logits, ("act_batch", "act_seq", "act_vocab"))


def encode(params, frames: jax.Array, cfg: ModelConfig,
           policy: CIMPolicy | None = None) -> jax.Array:
    """Whisper-style encoder over precomputed frame embeddings (stub
    frontend). Bidirectional attention, learned positions."""
    x = frames.astype(jnp.dtype(cfg.activation_dtype))
    b, s, _ = x.shape
    if cfg.learned_pos_emb:
        x = x + params["pos_emb"][:s][None].astype(x.dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    enc_cfg = cfg.replace(is_encoder_decoder=False,
                          layer_pattern=("attn",), moe=None)
    for j in range(cfg.n_encoder_layers):
        lp = params["encoder"][f"enc_{j:02d}"]
        h = _norm(lp["norm1"], x, cfg)
        # Bidirectional: full (non-causal) window = whole sequence.
        q, k, v = attention._project_qkv(lp["attn"], h, enc_cfg, policy)
        a = attention._gqa_core(q, k, v, None)
        a = common.linear_apply(
            lp["attn"]["wo"], a.reshape(b, s, enc_cfg.q_dim), policy)
        x = x + a
        h = _norm(lp["norm2"], x, cfg)
        x = x + common.mlp_apply(lp["mlp"], h, cfg.mlp_act, policy)
    return _norm(params["enc_norm"], x, cfg)


def forward_train(
    params: Params,
    batch: dict,
    cfg: ModelConfig,
    *,
    key: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Full forward; returns (logits, total_moe_aux)."""
    policy = cfg.cim
    x, positions = _embed_inputs(params, batch, cfg)
    p, n_units, n_tail = _unit_split(cfg)

    memory_kv_per_layer = None
    memory = None
    if cfg.is_encoder_decoder:
        memory = encode(params, batch["encoder_frames"], cfg, policy)

    def unit_body(carry, xs):
        x, aux = carry
        unit_params, unit_idx = xs
        for j in range(p):
            lkey = (
                None if key is None
                else jax.random.fold_in(key, unit_idx * p + j)
            )
            mkv = None
            lp = unit_params[f"layer_{j:02d}"]
            if memory is not None and "xattn" in lp:
                mkv = attention.encode_memory_kv(lp["xattn"], memory, cfg,
                                                 policy=policy)

            def one_layer(lp_, x_, j=j, lkey=lkey, mkv=mkv):
                return _layer_apply(
                    lp_, x_, cfg, j, positions=positions, policy=policy,
                    key=lkey, memory_kv=mkv,
                )

            if cfg.remat == "layer":
                one_layer = jax.checkpoint(one_layer)
            x, a = one_layer(lp, x)
            aux = aux + a
        return (x, aux), None

    aux = jnp.zeros((), jnp.float32)
    if n_units:
        body = _remat(unit_body, cfg)
        (x, aux), _ = jax.lax.scan(
            lambda c, xs: body(c, xs),
            (x, aux),
            (params["units"], jnp.arange(n_units, dtype=jnp.int32)),
        )
    for t in range(n_tail):
        li = n_units * p + t
        lkey = None if key is None else jax.random.fold_in(key, li)
        lp = params[f"tail_{t:02d}"]
        mkv = None
        if memory is not None and "xattn" in lp:
            mkv = attention.encode_memory_kv(lp["xattn"], memory, cfg,
                                             policy=policy)
        x, a = _layer_apply(lp, x, cfg, li, positions=positions,
                            policy=policy, key=lkey, memory_kv=mkv)
        aux = aux + a

    return _logits(params, x, cfg, policy), aux


def loss_fn(
    params: Params,
    batch: dict,
    cfg: ModelConfig,
    *,
    key: jax.Array | None = None,
) -> tuple[jax.Array, dict]:
    logits, aux = forward_train(params, batch, cfg, key=key)
    labels = batch["labels"]
    if cfg.frontend and "frontend_embeds" in batch:
        # Frontend positions carry no next-token loss; score text only.
        n_front = batch["frontend_embeds"].shape[1]
        logits = logits[:, n_front:]
    logits = constrain(logits.astype(jnp.float32),
                       ("act_batch", "act_seq", "act_vocab"))
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    aux_w = cfg.moe.aux_loss_weight if cfg.moe else 0.0
    total = loss + aux_w * aux
    return total, {"ce_loss": loss, "moe_aux": aux,
                   "tokens": jnp.sum(mask)}


# ---------------------------------------------------------------------------
# Serving path: caches, prefill, decode
# ---------------------------------------------------------------------------


def _layer_cache(cfg: ModelConfig, layer_idx: int, batch: int,
                 max_len: int, dtype):
    kind = cfg.layer_kind(layer_idx)
    # KV caches take cfg.kv_cache_dtype when it deviates from the
    # default (fp8 serving); recurrent states keep the caller's dtype
    # (their precision carries across the whole sequence).
    kv_dtype = dtype
    if cfg.kv_cache_dtype != "bfloat16":
        kv_dtype = jnp.dtype(cfg.kv_cache_dtype)
    if kind == "attn":
        return attention.init_cache(cfg, batch, max_len, dtype=kv_dtype)
    if kind == "attn_local":
        return attention.init_cache(cfg, batch, max_len,
                                    window=cfg.window_size,
                                    dtype=kv_dtype)
    if kind == "mamba":
        return mamba.init_cache(cfg, batch, dtype=dtype)
    return rwkv.init_cache(cfg, batch, dtype=dtype)


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16):
    p, n_units, n_tail = _unit_split(cfg)
    caches: dict = {}
    if n_units:
        unit = {
            f"layer_{j:02d}": _layer_cache(cfg, j, batch, max_len, dtype)
            for j in range(p)
        }
        caches["units"] = jax.tree.map(
            lambda a: jnp.broadcast_to(
                a[None], (n_units,) + a.shape
            ),
            unit,
        )
    for t in range(n_tail):
        li = n_units * p + t
        caches[f"tail_{t:02d}"] = _layer_cache(cfg, li, batch, max_len,
                                               dtype)
    return caches


def _layer_prefill(
    lp: Params,
    x: jax.Array,
    cfg: ModelConfig,
    layer_idx: int,
    cache,
    *,
    positions: jax.Array,
    policy: CIMPolicy | None,
    memory_kv=None,
):
    """Forward over the prompt while populating this layer's cache."""
    kind = cfg.layer_kind(layer_idx)
    h = _norm(lp["norm1"], x, cfg)
    if kind in ("attn", "attn_local"):
        window = cfg.window_size if kind == "attn_local" else 0
        a, cache = attention.prefill_cache(
            lp["attn"], h, cfg, cache, positions=positions, window=window,
            policy=policy,
        )
    elif kind == "mamba":
        a, mc = mamba.mamba_apply(lp["mamba"], h, cfg, policy=policy,
                                  return_cache=True)
        cache = jax.tree.map(lambda o, n: n.astype(o.dtype), cache, mc)
    else:  # rwkv
        a, s_tm, state = rwkv.timemix_apply(
            lp["tm"], h, cfg, wkv_state=cache.state.astype(jnp.float32),
            policy=policy,
        )
        cache = cache._replace(
            shift_tm=s_tm.astype(cache.shift_tm.dtype),
            state=state.astype(cache.state.dtype),
        )
    x = x + a.astype(x.dtype)
    if memory_kv is not None and "xattn" in lp:
        hx = _norm(lp["norm_x"], x, cfg)
        x = x + attention.cross_attend(lp["xattn"], hx, memory_kv, cfg,
                                       policy=policy)
    h = _norm(lp["norm2"], x, cfg)
    if kind == "rwkv":
        m, s_cm = rwkv.channelmix_apply(lp["cm"], h, cfg, policy=policy)
        cache = cache._replace(shift_cm=s_cm.astype(cache.shift_cm.dtype))
    elif "moe" in lp:
        m, _ = moe.moe_apply(lp["moe"], h, cfg, policy=policy)
    else:
        m = common.mlp_apply(lp["mlp"], h, cfg.mlp_act, policy)
    return x + m.astype(x.dtype), cache


def prefill(
    params: Params,
    tokens: jax.Array,  # [B, S] prompt
    caches,
    cfg: ModelConfig,
    *,
    memory: jax.Array | None = None,
) -> tuple[jax.Array, Any]:
    """Process the prompt; returns (last-position logits, caches)."""
    policy = cfg.cim
    x = common.embedding_apply(params["embed"], tokens)
    x = x.astype(jnp.dtype(cfg.activation_dtype))
    b, s, _ = x.shape
    if cfg.learned_pos_emb:
        x = x + params["pos_emb"][:s][None].astype(x.dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    p, n_units, n_tail = _unit_split(cfg)

    # Cache-as-carry: the stacked unit caches ride in the scan *carry*
    # and are updated in place with dynamic_update_index_in_dim. Passing
    # them as scan xs/ys instead allocates a second full cache buffer
    # (xs and ys cannot alias in an XLA while loop) -- measured +10 GiB
    # temp on qwen1.5-4b decode_32k.
    def unit_body(carry, xs):
        x, all_caches = carry
        unit_params, unit_idx = xs
        unit_cache = jax.tree.map(
            lambda c: jax.lax.dynamic_index_in_dim(
                c, unit_idx, 0, keepdims=False
            ),
            all_caches,
        )
        new_cache = {}
        for j in range(p):
            lp = unit_params[f"layer_{j:02d}"]
            mkv = None
            if memory is not None and "xattn" in lp:
                mkv = attention.encode_memory_kv(lp["xattn"], memory, cfg,
                                                 policy=policy)
            x, c = _layer_prefill(
                lp, x, cfg, j, unit_cache[f"layer_{j:02d}"],
                positions=positions, policy=policy, memory_kv=mkv,
            )
            new_cache[f"layer_{j:02d}"] = c
        all_caches = jax.tree.map(
            lambda allc, newc: jax.lax.dynamic_update_index_in_dim(
                allc, newc.astype(allc.dtype), unit_idx, 0
            ),
            all_caches,
            new_cache,
        )
        return (x, all_caches), None

    if n_units:
        (x, new_unit_caches), _ = jax.lax.scan(
            unit_body,
            (x, caches["units"]),
            (params["units"], jnp.arange(n_units, dtype=jnp.int32)),
        )
        caches = dict(caches)
        caches["units"] = new_unit_caches
    for t in range(n_tail):
        li = n_units * p + t
        lp = params[f"tail_{t:02d}"]
        mkv = None
        if memory is not None and "xattn" in lp:
            mkv = attention.encode_memory_kv(lp["xattn"], memory, cfg,
                                             policy=policy)
        x, c = _layer_prefill(lp, x, cfg, li, caches[f"tail_{t:02d}"],
                              positions=positions, policy=policy,
                              memory_kv=mkv)
        caches = dict(caches)
        caches[f"tail_{t:02d}"] = c

    logits = _logits(params, x[:, -1:], cfg, policy)
    return logits[:, 0], caches


def _layer_decode(
    lp: Params,
    x: jax.Array,
    cfg: ModelConfig,
    layer_idx: int,
    cache,
    pos: jax.Array,
    *,
    policy: CIMPolicy | None,
    memory_kv=None,
):
    kind = cfg.layer_kind(layer_idx)
    h = _norm(lp["norm1"], x, cfg)
    if kind in ("attn", "attn_local"):
        window = cfg.window_size if kind == "attn_local" else 0
        a, cache = attention.decode_step(lp["attn"], h, cfg, cache, pos,
                                         window=window, policy=policy)
    elif kind == "mamba":
        a, cache = mamba.mamba_decode_step(lp["mamba"], h, cfg, cache,
                                           policy=policy)
    else:  # rwkv: single-token timemix via the scan path (L=1)
        a, s_tm, state = rwkv.timemix_apply(
            lp["tm"], h.astype(cache.shift_tm.dtype), cfg,
            shift_state=cache.shift_tm, wkv_state=cache.state, chunk=1,
            policy=policy,
        )
        cache = cache._replace(
            shift_tm=s_tm.astype(cache.shift_tm.dtype),
            state=state.astype(cache.state.dtype),
        )
    x = x + a.astype(x.dtype)
    if memory_kv is not None and "xattn" in lp:
        hx = _norm(lp["norm_x"], x, cfg)
        x = x + attention.cross_attend(lp["xattn"], hx, memory_kv, cfg,
                                       policy=policy)
    h = _norm(lp["norm2"], x, cfg)
    if kind == "rwkv":
        m, s_cm = rwkv.channelmix_apply(
            lp["cm"], h.astype(cache.shift_cm.dtype), cfg,
            shift_state=cache.shift_cm, policy=policy)
        cache = cache._replace(shift_cm=s_cm.astype(cache.shift_cm.dtype))
    elif "moe" in lp:
        m, _ = moe.moe_apply(lp["moe"], h, cfg, policy=policy)
    else:
        m = common.mlp_apply(lp["mlp"], h, cfg.mlp_act, policy)
    return x + m.astype(x.dtype), cache


def decode_step(
    params: Params,
    token: jax.Array,  # [B] int32 current token
    pos: jax.Array,  # scalar int32 position
    caches,
    cfg: ModelConfig,
    *,
    memory: jax.Array | None = None,
) -> tuple[jax.Array, Any]:
    """One serving step: next-token logits + updated caches."""
    policy = cfg.cim
    x = common.embedding_apply(params["embed"], token[:, None])
    x = x.astype(jnp.dtype(cfg.activation_dtype))
    if cfg.learned_pos_emb:
        x = x + jax.lax.dynamic_slice_in_dim(
            params["pos_emb"], pos, 1, axis=0
        )[None].astype(x.dtype)
    p, n_units, n_tail = _unit_split(cfg)

    # Cache-as-carry (see prefill): in-place while-loop carry instead of
    # double-buffered scan xs/ys.
    def unit_body(carry, xs):
        x, all_caches = carry
        unit_params, unit_idx = xs
        unit_cache = jax.tree.map(
            lambda c: jax.lax.dynamic_index_in_dim(
                c, unit_idx, 0, keepdims=False
            ),
            all_caches,
        )
        new_cache = {}
        for j in range(p):
            lp = unit_params[f"layer_{j:02d}"]
            mkv = None
            if memory is not None and "xattn" in lp:
                mkv = attention.encode_memory_kv(lp["xattn"], memory, cfg,
                                                 policy=policy)
            x, c = _layer_decode(lp, x, cfg, j, unit_cache[f"layer_{j:02d}"],
                                 pos, policy=policy, memory_kv=mkv)
            new_cache[f"layer_{j:02d}"] = c
        all_caches = jax.tree.map(
            lambda allc, newc: jax.lax.dynamic_update_index_in_dim(
                allc, newc.astype(allc.dtype), unit_idx, 0
            ),
            all_caches,
            new_cache,
        )
        return (x, all_caches), None

    if n_units:
        (x, new_unit_caches), _ = jax.lax.scan(
            unit_body,
            (x, caches["units"]),
            (params["units"], jnp.arange(n_units, dtype=jnp.int32)),
        )
        caches = dict(caches)
        caches["units"] = new_unit_caches
    for t in range(n_tail):
        li = n_units * p + t
        lp = params[f"tail_{t:02d}"]
        mkv = None
        if memory is not None and "xattn" in lp:
            mkv = attention.encode_memory_kv(lp["xattn"], memory, cfg,
                                             policy=policy)
        x, c = _layer_decode(lp, x, cfg, li, caches[f"tail_{t:02d}"], pos,
                             policy=policy, memory_kv=mkv)
        caches = dict(caches)
        caches[f"tail_{t:02d}"] = c

    logits = _logits(params, x, cfg, policy)
    return logits[:, 0], caches
