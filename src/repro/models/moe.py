"""Mixture-of-Experts block.

Production path ('fp', dispatch='grouped'): GShard-style local routing
groups with capacity. Tokens are routed within groups of ~group_size by
one-hot dispatch/combine einsums, so every op keeps a leading group dim
that shards over the data axes -- fully SPMD-partitionable (a global
argsort would force GSPMD to replicate the sort: measured 1.9 TiB temp
on qwen2-moe prefill_32k). Expert FLOPs scale with capacity ~= top_k *
capacity_factor, so the roofline table reflects honest MoE compute
(6 * N_active * D); dispatch-einsum overhead is ~2*Tg*k*cf*d per token
(~1-2% of model FLOPs at group_size 4096).

dispatch='ragged' keeps the exact argsort + lax.ragged_dot path (no
token drops) for single-host tests and small studies.

Sharding: experts' hidden dim ('mlp' logical axis) is tensor-parallel
over 'model'; for inference the expert dim is expert-parallel over
'data' (INFERENCE_RULES). The router is always digital (CIM-exempt;
see DESIGN.md Sec. 5 arch-applicability).

CIM path: per-expert masked dense loop (exact, E/k x more compute) --
used only for small-scale accuracy studies.

Shared experts (qwen2-moe): one fused SwiGLU of width n_shared*d_expert
with a sigmoid gate, per the Qwen1.5-MoE design.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import CIMPolicy, MoEConfig, ModelConfig
from repro.models import common
from repro.models.common import ParamSpec


class MoEMetrics(NamedTuple):
    aux_loss: jax.Array  # load-balance loss (scalar)
    router_entropy: jax.Array


def moe_spec(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    mo = cfg.moe
    assert mo is not None
    spec = {
        "router": {"w": ParamSpec((d, mo.n_experts), ("embed", "experts"),
                                  "normal:0.02")},
        "gate": ParamSpec((mo.n_experts, d, mo.d_expert),
                          ("experts", "embed", "mlp"), "fanin"),
        "up": ParamSpec((mo.n_experts, d, mo.d_expert),
                        ("experts", "embed", "mlp"), "fanin"),
        "down": ParamSpec((mo.n_experts, mo.d_expert, d),
                          ("experts", "mlp", "embed"), "fanin"),
    }
    if mo.d_shared:
        spec["shared"] = common.mlp_spec(d, mo.d_shared, "silu")
        spec["shared_gate"] = {"w": ParamSpec((d, 1), ("embed", None),
                                              "normal:0.02")}
    return spec


def _router(params, x2, mo: MoEConfig, key=None):
    """x2: [T, d] -> (top_p [T,k], top_e [T,k], metrics)."""
    logits = x2 @ params["router"]["w"].astype(x2.dtype)  # digital
    if mo.router_jitter and key is not None:
        logits = logits + mo.router_jitter * jax.random.normal(
            key, logits.shape
        )
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, mo.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # Load-balance aux loss (Switch-style): E * sum_e f_e * P_e.
    e = mo.n_experts
    f = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(
        1.0 / top_e.size
    )
    p_mean = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * p_mean)
    entropy = -jnp.mean(jnp.sum(probs * jnp.log(probs + 1e-9), axis=-1))
    return top_p.astype(x2.dtype), top_e, MoEMetrics(aux, entropy)


def _bank(params, name, dtype):
    """Expert weight bank, reading through the planned (int8 serving /
    CIM) representation when the tree was transformed by plan_params."""
    from repro.serve.quantized import maybe_dequant

    return maybe_dequant(params[name], dtype)


def _experts_ragged(params, xs, group_sizes, dtype):
    """SwiGLU over contiguous expert segments via ragged_dot."""
    g = jax.lax.ragged_dot(xs, _bank(params, "gate", dtype), group_sizes)
    u = jax.lax.ragged_dot(xs, _bank(params, "up", dtype), group_sizes)
    h = jax.nn.silu(g) * u
    return jax.lax.ragged_dot(h, _bank(params, "down", dtype),
                              group_sizes)


def _capacity(t_group: int, mo: MoEConfig) -> int:
    cap = int(t_group * mo.top_k * mo.capacity_factor / mo.n_experts)
    return max(cap, mo.top_k)


def _constrain_expert_buffer(xe):
    """Shard the [G, E, C, d] dispatch buffer: routing groups over the
    data axes when G divides (training / prefill: everything local);
    otherwise expert-parallel over data (decode: G==1, tokens are tiny
    but the expert bank is not -- without this GSPMD un-does EP by
    all-gathering the expert weights; measured +19 GiB on jamba
    decode_32k)."""
    from repro.distributed.sharding import (  # local import: no cycle
        _ctx_mesh, _entry, _greedy_axes,
    )

    mesh = _ctx_mesh()
    if mesh is None:
        return xe
    g, e = xe.shape[0], xe.shape[1]
    used: set = set()
    g_ax = _greedy_axes(g, ("pod", "data"), mesh, used)
    e_ax = _greedy_axes(e, ("pod", "data"), mesh, used)
    spec = jax.sharding.PartitionSpec(
        _entry(g_ax), _entry(e_ax), None, None)
    return jax.lax.with_sharding_constraint(xe, spec)


def _dispatch_grouped(params, x2, top_p, top_e, mo: MoEConfig, dtype):
    """GShard-style grouped capacity dispatch (SPMD-partitionable).

    Tokens are split into local routing groups of ~group_size; within a
    group, each token's k-th choice claims a slot in its expert's queue
    (capacity C = Tg*k*cf/E); overflow tokens are dropped for that
    choice (their combine weight is zero). Every tensor keeps a leading
    group dim that shards over the data axes -- no global sort, no
    replication (GShard/Switch local-group routing).

    Routing into the [G, E, C, d] buffers uses batched scatter/gather
    (vmap over G -> one XLA scatter with a batching dim) instead of
    one-hot dispatch einsums: the [G, Tg, E, C] mask tensors cost
    T*Tg*k*cf floats and 2*T*Tg*k*cf*d dispatch FLOPs -- measured
    42 GiB temp on granite train_4k (top_k=8), with more einsum FLOPs
    than the experts themselves. Scatter/gather moves O(T*k*d) bytes
    and adds zero matmul FLOPs. The paper-faithful CIM path is
    unaffected (dense per-expert loop at study scale).
    """
    t, d = x2.shape
    e, k = mo.n_experts, mo.top_k
    g = max(1, t // mo.group_size)
    while t % g:  # t is B*S; fall back to fewer groups if ragged
        g -= 1
    tg = t // g
    cap = _capacity(tg, mo)

    xg = x2.reshape(g, tg, d)
    eg = top_e.reshape(g, tg, k)
    pg = top_p.reshape(g, tg, k).astype(jnp.float32)

    # [G, Tg, k, E] one-hot of the chosen expert per (token, choice).
    onehot = jax.nn.one_hot(eg, e, dtype=jnp.float32)
    # Queue position of each (token, choice) in its expert, priority by
    # (choice slot, then token order) -- flatten (k, t) choice-major so
    # first choices always beat second choices for capacity.
    flat = onehot.transpose(0, 2, 1, 3).reshape(g, k * tg, e)
    pos_flat = jnp.cumsum(flat, axis=1) - flat  # [G, k*Tg, E]
    pos = pos_flat.reshape(g, k, tg, e).transpose(0, 2, 1, 3)
    keep = (pos < cap) * onehot  # [G, Tg, k, E]
    kept = jnp.sum(keep, axis=-1)  # [G, Tg, k] in {0, 1}
    slot = jnp.sum(pos * keep, axis=-1).astype(jnp.int32)  # [G,Tg,k]

    # Scatter tokens into the per-expert queues [G, E, C, d]. Dropped
    # choices scatter zeros into slot 0 (harmless) and combine with
    # weight zero.
    upd = (xg[:, :, None, :] * kept[..., None]).astype(dtype)

    def scat(e_i, s_i, u):  # one routing group
        return jnp.zeros((e, cap, d), dtype).at[
            e_i.reshape(-1), s_i.reshape(-1)
        ].add(u.reshape(-1, d))

    xe = jax.vmap(scat)(eg, slot, upd)  # [G, E, C, d]
    xe = _constrain_expert_buffer(xe)

    gate = jnp.einsum("gecd,edf->gecf", xe, _bank(params, "gate", dtype))
    up = jnp.einsum("gecd,edf->gecf", xe, _bank(params, "up", dtype))
    h = jax.nn.silu(gate) * up
    ye = jnp.einsum("gecf,efd->gecd", h, _bank(params, "down", dtype))
    ye = _constrain_expert_buffer(ye)

    # Gather each kept choice's output back to its token; combine.
    def gath(ye_g, e_i, s_i):
        return ye_g[e_i.reshape(-1), s_i.reshape(-1)].reshape(tg, k, d)

    yt = jax.vmap(gath)(ye, eg, slot)  # [G, Tg, k, d]
    out = jnp.einsum("gtkd,gtk->gtd", yt, (pg * kept).astype(dtype))
    return out.reshape(t, d)


def _experts_dense_cim(params, x2, top_p, top_e, mo, policy, key):
    """Masked per-expert loop through the CIM macro (accuracy studies)."""
    t, d = x2.shape
    out = jnp.zeros((t, d), x2.dtype)
    for e in range(mo.n_experts):
        w_e = (
            jnp.sum(jnp.where(top_e == e, top_p, 0.0), axis=-1)
        )  # [T]
        ek = None if key is None else jax.random.fold_in(key, e)
        eks = (None,) * 3 if ek is None else jax.random.split(ek, 3)
        # Expert e's slice of each bank (of every stored tensor of a
        # planned bank).
        w = {n: jax.tree.map(lambda a, e=e: a[e], params[n])
             for n in ("gate", "up", "down")}
        g = common.linear_apply({"w": w["gate"]}, x2, policy, key=eks[0])
        u = common.linear_apply({"w": w["up"]}, x2, policy, key=eks[1])
        h = jax.nn.silu(g) * u
        y = common.linear_apply({"w": w["down"]}, h, policy, key=eks[2])
        out = out + w_e[:, None] * y
    return out


def moe_apply(
    params: dict,
    x: jax.Array,  # [B, S, D]
    cfg: ModelConfig,
    *,
    policy: CIMPolicy | None = None,
    key: jax.Array | None = None,
) -> tuple[jax.Array, MoEMetrics]:
    mo = cfg.moe
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    t = b * s

    rkey = None if key is None else jax.random.fold_in(key, 0)
    top_p, top_e, metrics = _router(params, x2, mo, key=rkey)

    use_cim = (
        policy is not None
        and policy.mode != "fp"
        and policy.apply_to_experts
    )
    if use_cim:
        out = _experts_dense_cim(params, x2, top_p, top_e, mo, policy, key)
    elif mo.dispatch == "grouped":
        out = _dispatch_grouped(params, x2, top_p, top_e, mo, x2.dtype)
    else:  # 'ragged': exact single-host path (tests, small studies)
        flat_e = top_e.reshape(-1)  # [T*k]
        order = jnp.argsort(flat_e)
        token_of = order // mo.top_k
        xs = jnp.take(x2, token_of, axis=0)  # [T*k, d]
        group_sizes = jnp.zeros((mo.n_experts,), jnp.int32).at[flat_e].add(1)
        ys = _experts_ragged(params, xs, group_sizes, x2.dtype)
        p_sorted = jnp.take(top_p.reshape(-1), order)
        out = jnp.zeros((t, d), x2.dtype).at[token_of].add(
            ys * p_sorted[:, None]
        )

    if mo.d_shared:
        sh = common.mlp_apply(params["shared"], x2, "silu", policy, key=key)
        gate = jax.nn.sigmoid(
            x2 @ params["shared_gate"]["w"].astype(x2.dtype)
        )
        out = out + gate * sh

    return out.reshape(b, s, d), metrics
