"""Logical-axis sharding rules (MaxText-style, one source of truth).

Every parameter/cache/batch tensor carries a tuple of *logical* axis
names (assigned in the model specs); this module maps them onto mesh
axes with divisibility-aware fallback:

  vocab/heads/kv_heads/mlp -> 'model'   (tensor parallel)
  embed                    -> 'data'    (FSDP: weights sharded over DP)
  batch                    -> ('pod', 'data')
  cache_seq                -> ('pod', 'data')  (sequence-parallel KV for
                              batch=1 long-context decode; only applies
                              when 'batch' could not use those axes)
  experts/layers           -> unsharded (EP is TP-within-expert; layers
                              is the scan dim)

A mesh axis is consumed at most once per tensor; a logical axis whose
dim is not divisible by the mesh axis size silently degrades to
replicated (e.g. whisper's 6 kv-heads on a 16-wide model axis), which
GSPMD then propagates -- correctness never depends on the rule table.
"""

from __future__ import annotations

import re
from typing import Any, Mapping

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "embed": ("data",),
    "experts": (),
    "layers": (),
    "batch": ("pod", "data"),
    "cache_seq": ("pod", "data"),
    "seq": (),
}

# Inference (prefill/decode) parameter rules: weights stay *stationary*
# (TP over 'model' only; replicated over 'data'), because FSDP-style
# 'embed'-over-data sharding forces a full-parameter all-gather every
# step -- measured +16 GiB temp on qwen1.5-4b decode. MoE expert banks
# are instead expert-parallel over 'data' (jamba's 700 GB of experts
# cannot replicate 16x).
INFERENCE_RULES: dict[str, tuple[str, ...]] = {
    **DEFAULT_RULES,
    "embed": (),
    "experts": ("data",),
}


def spec_for(
    axes: tuple[str | None, ...],
    shape: tuple[int, ...],
    mesh: Mesh,
    rules: Mapping[str, tuple[str, ...]] | None = None,
) -> PartitionSpec:
    """PartitionSpec for one tensor, divisibility-aware, no axis reuse."""
    rules = DEFAULT_RULES if rules is None else rules
    used: set[str] = set()
    entries: list[Any] = []
    for dim, ax in zip(shape, axes, strict=False):
        if ax is None or ax not in rules:
            entries.append(None)
            continue
        assigned: list[str] = []
        factor = 1
        for mesh_ax in rules[ax]:
            if mesh_ax in used or mesh_ax not in mesh.shape:
                continue
            size = mesh.shape[mesh_ax]
            if dim % (factor * size) == 0:
                assigned.append(mesh_ax)
                used.add(mesh_ax)
                factor *= size
        if not assigned:
            entries.append(None)
        elif len(assigned) == 1:
            entries.append(assigned[0])
        else:
            entries.append(tuple(assigned))
    return PartitionSpec(*entries)


def sharding_for(
    axes: tuple[str | None, ...],
    shape: tuple[int, ...],
    mesh: Mesh | None,
    rules: Mapping[str, tuple[str, ...]] | None = None,
) -> NamedSharding | None:
    if mesh is None:  # probe/unsharded path
        return None
    return NamedSharding(mesh, spec_for(axes, shape, mesh, rules))


def tree_shardings(
    axes_tree: Any,
    shape_tree: Any,
    mesh: Mesh | None,
    rules: Mapping[str, tuple[str, ...]] | None = None,
) -> Any:
    """Map matching (axes, ShapeDtypeStruct) trees -> NamedSharding tree."""
    if mesh is None:
        return None
    is_axes = lambda x: isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x
    )
    return jax.tree.map(
        lambda ax, sds: sharding_for(ax, tuple(sds.shape), mesh, rules),
        axes_tree,
        shape_tree,
        is_leaf=is_axes,
    )


# ---------------------------------------------------------------------------
# Name-based axes for caches and batches (leaf-name conventions)
# ---------------------------------------------------------------------------

_CACHE_AXES = {
    "k": ("batch", "cache_seq", "kv_heads", None),
    "v": ("batch", "cache_seq", "kv_heads", None),
    "conv": ("batch", None, "mlp"),
    "ssm": ("batch", "mlp", None),
    "state": ("batch", "heads", None, None),
    "shift_tm": ("batch", None),
    "shift_cm": ("batch", None),
}

_BATCH_AXES = {
    "tokens": ("batch", "seq"),
    "labels": ("batch", "seq"),
    "frontend_embeds": ("batch", None, None),
    "encoder_frames": ("batch", None, None),
    "image": ("batch", None, None, None),
    "label": ("batch",),
}


def _leaf_name(path) -> str:
    for p in reversed(path):
        if hasattr(p, "key"):
            return str(p.key)
        if hasattr(p, "name"):
            return str(p.name)
    return ""


def cache_axes(cache_tree: Any) -> Any:
    """Logical axes for a cache pytree by leaf-name convention.

    Caches stacked under a scanned 'units' group gain a leading
    'layers' axis (detected by ndim excess).
    """

    def one(path, leaf):
        name = _leaf_name(path)
        base = _CACHE_AXES.get(name)
        if base is None:
            raise KeyError(f"unknown cache leaf '{name}'")
        if len(leaf.shape) == len(base) + 1:
            return ("layers",) + base
        assert len(leaf.shape) == len(base), (name, leaf.shape)
        return base

    return jax.tree_util.tree_map_with_path(one, cache_tree)


def batch_axes(batch: Any) -> Any:
    def one(path, leaf):
        name = _leaf_name(path)
        base = _BATCH_AXES.get(name)
        if base is None:
            base = ("batch",) + (None,) * (len(leaf.shape) - 1)
        return base

    return jax.tree_util.tree_map_with_path(one, batch)


def _greedy_axes(
    dim: int, candidates: tuple[str, ...], mesh: Mesh, used: set[str]
) -> list[str]:
    got: list[str] = []
    factor = 1
    for ax in candidates:
        if ax in used or ax not in mesh.shape:
            continue
        size = mesh.shape[ax]
        if dim % (factor * size) == 0:
            got.append(ax)
            used.add(ax)
            factor *= size
    return got


def _entry(axs: list[str]):
    if not axs:
        return None
    return axs[0] if len(axs) == 1 else tuple(axs)


def kv_cache_spec(shape: tuple[int, ...], mesh: Mesh) -> PartitionSpec:
    """KV cache [(layers,) B, S, KVH, hd] with cross-dim fallback.

    Priority: batch <- (pod, data); kv_heads <- model; seq <- whatever
    mesh axes remain. The fallback is what makes decode cells fit HBM
    for archs whose kv-head count does not divide the model axis
    (qwen1.5: 20 kv-heads, yi-34b: 8) -- the 32k/500k cache then shards
    its *sequence* dim over the idle axes instead of replicating
    terabytes. GSPMD turns attention over a seq-sharded cache into
    partial-softmax + small reductions (the scores tensor, not the
    cache, crosses the links).
    """
    lead = len(shape) - 4
    b, s, kvh, _ = shape[lead:]
    used: set[str] = set()
    b_ax = _greedy_axes(b, ("pod", "data"), mesh, used)
    h_ax = _greedy_axes(kvh, ("model",), mesh, used)
    s_ax = _greedy_axes(s, ("model", "pod", "data"), mesh, used)
    return PartitionSpec(
        *((None,) * lead), _entry(b_ax), _entry(s_ax), _entry(h_ax), None
    )


def cache_shardings(cache_tree: Any, mesh: Mesh | None) -> Any:
    """NamedShardings for a serving-cache pytree.

    k/v leaves get the cross-dim-fallback spec above; SSM/RWKV state
    leaves go through the generic rule table (their dims are O(1) in
    seq, so the generic table suffices).
    """
    if mesh is None:
        return None

    def one(path, leaf):
        name = _leaf_name(path)
        shape = tuple(leaf.shape)
        if name in ("k", "v"):
            return NamedSharding(mesh, kv_cache_spec(shape, mesh))
        base = _CACHE_AXES[name]
        if len(shape) == len(base) + 1:
            base = ("layers",) + base
        return sharding_for(base, shape, mesh)

    return jax.tree_util.tree_map_with_path(one, cache_tree)


# ---------------------------------------------------------------------------
# Planned-weight (PlannedWeights) sharding: plan-aware serving
# ---------------------------------------------------------------------------


def _last_dim_model(shape: tuple[int, ...], mesh: Mesh) -> NamedSharding:
    """Shard the trailing (output-channel) dim over 'model' if divisible."""
    axs = _greedy_axes(shape[-1], ("model",), mesh, set())
    return NamedSharding(
        mesh, PartitionSpec(*((None,) * (len(shape) - 1)), _entry(axs))
    )


def plan_shardings(plan: Any, mesh: Mesh) -> Any:
    """NamedShardings for one ``engine.PlannedWeights``.

    Every stored-weight tensor is tensor-parallel over the model axis
    on its output-channel (N) dim — codes [..., K, N], kept fp weights
    and the pre-grouped ``planes`` in BOTH storage forms (unpacked
    [G, B, rows, N] int8 and bit-packed [G, rows, N] uint8): the
    group/plane/row dims are the contraction structure and must stay
    local to a shard, while N is embarrassingly parallel — each model
    shard holds the planes of its own output columns, so planned decode
    scales across devices without re-planning (divisibility-aware: an
    indivisible N degrades to replicated, like every rule here).

    The [..., 1, N] epilogue vectors (scale, colsum) stay whole: a
    step under :func:`per_device` gathers the integer macro result and
    runs the epilogue on whole rows (see ``engine.PlannedWeights``).
    ``slots`` stay whole too: their slot-major last dim does not split
    by output column, and the per-device view does not use them.
    """
    import dataclasses as _dc

    def one(v):
        return None if v is None else _last_dim_model(tuple(v.shape), mesh)

    def whole(v):
        return None if v is None else replicated(mesh)

    return _dc.replace(
        plan,
        codes=one(plan.codes),
        scale=whole(plan.scale),
        colsum=whole(plan.colsum),
        w=one(plan.w),
        planes=one(plan.planes),
        slots=whole(plan.slots),
    )


def planned_param_shardings(
    planned_tree: Any, mesh: Mesh | None
) -> Any:
    """Shardings for a whole ``engine.plan_params`` tree.

    PlannedWeights leaves get :func:`plan_shardings`; unplanned leaves
    (norms, embeddings, biases) stay replicated — weight-stationary
    inference replicates them by design (see INFERENCE_RULES).
    """
    if mesh is None:
        return None
    from repro.core.engine import PlannedWeights  # lazy: keep import light

    def one(node):
        if isinstance(node, PlannedWeights):
            return plan_shardings(node, mesh)
        return replicated(mesh)

    return jax.tree.map(
        one, planned_tree,
        is_leaf=lambda x: isinstance(x, PlannedWeights),
    )


def shard_planned(planned_tree: Any, mesh: Mesh | None) -> Any:
    """device_put a planned tree under :func:`planned_param_shardings`."""
    if mesh is None:
        return planned_tree
    return jax.device_put(
        planned_tree, planned_param_shardings(planned_tree, mesh)
    )


def per_device(fn, planned_tree: Any, mesh: Mesh):
    """Run ``fn(params, *args)`` once on every device of ``mesh``.

    ``params`` must be laid out as :func:`shard_planned` places
    ``planned_tree``; every other argument and every output is whole on
    each device. Inside, a plan whose output columns are split arrives
    as a column shard (``PlannedWeights.column_axis`` set, its whole
    ``slots`` dropped), so each of its reads gathers whole columns and
    ``fn`` computes what it computes on one device, plus the gathers:
    the weight matmuls split across devices, all other work (epilogue,
    norms, attention, caches) is repeated on each. Mesh axes other than
    'model' split nothing.
    """
    import dataclasses as _dc

    from repro.core.engine import PlannedWeights  # lazy: keep import light

    shardings = planned_param_shardings(planned_tree, mesh)

    def is_plan(x):
        return isinstance(x, PlannedWeights)

    def view(node, sh):
        if not is_plan(node) or sh.codes.spec[-1] is None:
            return node
        return _dc.replace(node, column_axis=sh.codes.spec[-1], slots=None)

    def body(params, args):
        params = jax.tree.map(view, params, shardings, is_leaf=is_plan)
        return fn(params, *args)

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda s: s.spec, shardings),
                  PartitionSpec()),
        out_specs=PartitionSpec(), check_vma=False,
    )
    return lambda params, *args: mapped(params, args)


def opt_state_axes(param_axes: Any, opt_state) -> Any:
    """AdamW m/v inherit the param axes; step/rng are replicated."""
    from repro.optim.adamw import AdamWState

    return AdamWState(
        step=(),
        m=param_axes,
        v=jax.tree.map(lambda a: a, param_axes),
    )


def replicated(mesh: Mesh | None) -> NamedSharding | None:
    if mesh is None:
        return None
    return NamedSharding(mesh, PartitionSpec())


# ---------------------------------------------------------------------------
# Activation sharding constraints (annotations inside model code)
# ---------------------------------------------------------------------------

_ACT_RULES: dict[str, tuple[str, ...]] = {
    "act_batch": ("pod", "data"),
    "act_seq": (),
    "act_vocab": ("model",),
    "act_heads": ("model",),
    "act_mlp": ("model",),
    "act_embed": (),
}


def constrain(x, axes: tuple[str | None, ...]):
    """with_sharding_constraint by logical activation axes.

    No-op when no mesh context (``jax.set_mesh``) is active
    (probe/smoke paths); a dim not divisible by its mesh axes stays
    unconstrained. Errors from the constraint itself propagate: a
    broken layout must not silently run unsharded. Model code calls
    this at the few propagation cliffs (logits, embed output, FFN
    hidden) -- the MaxText pattern.
    """
    mesh = _ctx_mesh()
    if mesh is None:
        return x
    entries = []
    for dim, ax in zip(x.shape, axes, strict=False):
        names = []
        factor = 1
        if ax is not None:
            for mesh_ax in _ACT_RULES.get(ax, ()):
                if mesh_ax not in mesh.shape:
                    continue
                size = mesh.shape[mesh_ax]
                if dim % (factor * size) == 0:
                    names.append(mesh_ax)
                    factor *= size
        if not names:
            entries.append(None)
        elif len(names) == 1:
            entries.append(names[0])
        else:
            entries.append(tuple(names))
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*entries))


def _ctx_mesh():
    """The ``jax.set_mesh`` context mesh, or None outside one.

    None inside a ``shard_map`` as well: its specs fix the body's
    layout, and its axes are manual, which a constraint may not name.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not mesh.shape or mesh.manual_axes:
        return None
    return mesh


def constrain_query(q):
    """Shard the query tensor [B, S, H, hd] for the attention core.

    Priority: heads (H) on 'model' (tensor parallel); query-seq (S)
    fallback (context parallel) for archs whose head counts don't
    divide the model axis (qwen2-0.5b: 14 heads on a 16-wide axis).
    Constraining q (one producer) instead of the score tensor lets the
    SPMD solver pick consistent dot strategies downstream.
    """
    mesh = _ctx_mesh()
    if mesh is None:
        return q
    b, s, h, _ = q.shape
    batch_axes = []
    factor = 1
    for ax in ("pod", "data"):
        if ax in mesh.shape and b % (factor * mesh.shape[ax]) == 0:
            batch_axes.append(ax)
            factor *= mesh.shape[ax]
    bspec = (
        None if not batch_axes
        else batch_axes[0] if len(batch_axes) == 1
        else tuple(batch_axes)
    )
    model = mesh.shape.get("model", 1)
    spec = [bspec, None, None, None]
    if model > 1:
        if h % model == 0:
            spec[2] = "model"
        elif s % model == 0:
            spec[1] = "model"
    return jax.lax.with_sharding_constraint(q, PartitionSpec(*spec))
