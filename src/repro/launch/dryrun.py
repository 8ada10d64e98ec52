"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi_34b \
      --shape train_4k --mesh single --out results/dryrun.json
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Per cell this records:
  * compile success (the deliverable: sharding/partitioning coherence),
  * memory_analysis (per-device bytes: args/output/temp -> fits HBM?),
  * cost_analysis flops/bytes of the per-device program,
  * collective inventory parsed from the compiled HLO (op kind ->
    operand bytes), feeding the roofline collective term,
  * a FLOPs probe: cost_analysis counts lax.scan bodies ONCE (measured,
    see EXPERIMENTS.md Sec. Methodology), so scanned-layer lowerings
    undercount. The probe lowers unrolled 1-unit and 2-unit variants of
    the model; per-unit flops = f(2u) - f(1u), total = f(1u) +
    (n_units_effective - 1) * per_unit. Sequential time-recurrences
    (WKV) get documented analytic corrections.
"""

# The first two lines MUST run before any jax import: jax locks the
# device count at first initialization.
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import (  # noqa: E402
    ARCH_IDS,
    SHAPES,
    ModelConfig,
    ShapeConfig,
    get_config,
    shape_cells,
)
from repro.distributed import sharding as shd  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import common, transformer  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.train import trainer as trainer_lib  # noqa: E402

F32 = jnp.float32
BF16 = jnp.bfloat16
I32 = jnp.int32


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; never allocate)
# ---------------------------------------------------------------------------


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def batch_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    b, s = shape.global_batch, shape.seq_len
    batch = {
        "tokens": sds((b, s), I32),
        "labels": sds((b, s), I32),
    }
    if cfg.frontend == "vision_patches":
        # Patch tokens are part of the assigned seq budget.
        batch["tokens"] = sds((b, s - cfg.frontend_seq), I32)
        batch["labels"] = sds((b, s - cfg.frontend_seq), I32)
        batch["frontend_embeds"] = sds(
            (b, cfg.frontend_seq, cfg.d_model), F32
        )
    if cfg.is_encoder_decoder:
        batch["encoder_frames"] = sds(
            (b, cfg.frontend_seq, cfg.d_model), F32
        )
    return batch


def params_specs(cfg: ModelConfig):
    spec_tree = transformer.model_spec(cfg)
    dtype = jnp.dtype(cfg.param_dtype)
    return jax.tree.map(
        lambda s: sds(s.shape, dtype),
        spec_tree,
        is_leaf=lambda x: isinstance(x, common.ParamSpec),
    )


def state_specs(cfg: ModelConfig):
    p = params_specs(cfg)
    opt_dtype = jnp.dtype(cfg.opt_state_dtype)
    zeros = jax.tree.map(lambda s: sds(s.shape, opt_dtype), p)
    return trainer_lib.TrainState(
        params=p,
        opt=adamw.AdamWState(step=sds((), I32), m=zeros,
                             v=jax.tree.map(lambda s: s, zeros)),
        comp=None,
        rng=sds((2,), jnp.uint32),
    )


def cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    return jax.eval_shape(
        lambda: transformer.init_caches(cfg, batch, max_len, dtype=BF16)
    )


# ---------------------------------------------------------------------------
# Step builders: (fn, arg_specs, in_shardings, out_shardings)
# ---------------------------------------------------------------------------


def build_train_step(cfg: ModelConfig, shape: ShapeConfig, mesh):
    opt_cfg = adamw.OptimizerConfig()

    def loss(params, batch, key):
        return transformer.loss_fn(params, batch, cfg, key=key)

    step = trainer_lib.make_train_step(
        loss, opt_cfg,
        microbatches=cfg.microbatches,
        accum_dtype=jnp.dtype(cfg.grad_accum_dtype),
        jit=False,
    )

    st = state_specs(cfg)
    bt = batch_specs(cfg, shape)
    ax = transformer.model_axes(cfg)
    p_sh = shd.tree_shardings(ax, st.params, mesh)
    opt_sh = adamw.AdamWState(
        step=shd.replicated(mesh),
        m=shd.tree_shardings(ax, st.opt.m, mesh),
        v=shd.tree_shardings(ax, st.opt.v, mesh),
    )
    st_sh = trainer_lib.TrainState(
        params=p_sh, opt=opt_sh, comp=None, rng=shd.replicated(mesh)
    )
    b_sh = shd.tree_shardings(shd.batch_axes(bt), bt, mesh)
    in_sh = (st_sh, b_sh)
    # metrics replicated; out state shardings mirror input.
    out_sh = (st_sh, None)
    return step, (st, bt), in_sh, out_sh, {"donate_argnums": (0,)}


def build_prefill_step(cfg: ModelConfig, shape: ShapeConfig, mesh,
                       serve_quant: bool = False):
    b, s = shape.global_batch, shape.seq_len

    memory_spec = None
    if cfg.is_encoder_decoder:
        memory_spec = sds((b, cfg.frontend_seq, cfg.d_model), BF16)

        def fn(params, tokens, caches, memory):
            return transformer.prefill(params, tokens, caches, cfg,
                                       memory=memory)
    else:

        def fn(params, tokens, caches):
            return transformer.prefill(params, tokens, caches, cfg)

    ps = params_specs(cfg)
    cs = cache_specs(cfg, b, s)
    tok = sds((b, s), I32)
    ax = transformer.model_axes(cfg)
    if serve_quant:  # int8 weight-only serving (EXPERIMENTS Sec. 6)
        from repro.serve import quantized as sq
        ps = sq.quantize_params_for_serving(ps)
        ax = sq.quantize_axes_for_serving(ax)
    p_sh = shd.tree_shardings(ax, ps, mesh, shd.INFERENCE_RULES)
    c_sh = shd.cache_shardings(cs, mesh)
    t_sh = shd.sharding_for(("batch", "seq"), (b, s), mesh)
    args = (ps, tok, cs) + ((memory_spec,) if memory_spec else ())
    in_sh = (p_sh, t_sh, c_sh) + (
        (shd.sharding_for(("batch", None, None), memory_spec.shape, mesh),)
        if memory_spec
        else ()
    )
    out_sh = (
        shd.sharding_for(("batch", "vocab"), (b, cfg.padded_vocab), mesh),
        c_sh,
    )
    return fn, args, in_sh, out_sh, {"donate_argnums": (2,)}


def build_decode_step(cfg: ModelConfig, shape: ShapeConfig, mesh,
                      serve_quant: bool = False):
    b, s = shape.global_batch, shape.seq_len

    memory_spec = None
    if cfg.is_encoder_decoder:
        memory_spec = sds((b, cfg.frontend_seq, cfg.d_model), BF16)

        def fn(params, token, pos, caches, memory):
            return transformer.decode_step(params, token, pos, caches, cfg,
                                           memory=memory)
    else:

        def fn(params, token, pos, caches):
            return transformer.decode_step(params, token, pos, caches, cfg)

    ps = params_specs(cfg)
    cs = cache_specs(cfg, b, s)
    ax = transformer.model_axes(cfg)
    if serve_quant:  # int8 weight-only serving (EXPERIMENTS Sec. 6)
        from repro.serve import quantized as sq
        ps = sq.quantize_params_for_serving(ps)
        ax = sq.quantize_axes_for_serving(ax)
    p_sh = shd.tree_shardings(ax, ps, mesh, shd.INFERENCE_RULES)
    c_sh = shd.cache_shardings(cs, mesh)
    tok = sds((b,), I32)
    pos = sds((), I32)
    args = (ps, tok, pos, cs) + ((memory_spec,) if memory_spec else ())
    in_sh = (
        p_sh,
        shd.sharding_for(("batch",), (b,), mesh),
        shd.replicated(mesh),
        c_sh,
    ) + (
        (shd.sharding_for(("batch", None, None), memory_spec.shape, mesh),)
        if memory_spec
        else ()
    )
    out_sh = (
        shd.sharding_for(("batch", "vocab"), (b, cfg.padded_vocab), mesh),
        c_sh,
    )
    return fn, args, in_sh, out_sh, {"donate_argnums": (3,)}


_BUILDERS = {
    "train": build_train_step,
    "prefill": build_prefill_step,
    "decode": build_decode_step,
}


# ---------------------------------------------------------------------------
# HLO collective inventory
# ---------------------------------------------------------------------------

_COLL_RE = re.compile(
    r"\b"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_TYPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _tensor_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # iota form: replica_groups=[num_groups,group_size]<=[n]
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        body = m.group(1).strip()
        return body.count(",") + 1 if body else 1
    return 1


def collective_inventory(hlo_text: str) -> dict:
    """Per-kind collective traffic from compiled HLO text.

    Compiled HLO prints operands as bare names (no types), so we read
    the *result* types (everything left of the op name on its line)
    plus the replica group size G, and convert to per-device link
    traffic with the standard ring costs:
      all-gather         result * (G-1)/G   (receives the other shards)
      reduce-scatter     result * (G-1)     (input = result * G)
      all-reduce         2 * result * (G-1)/G   (RS + AG)
      all-to-all         result * (G-1)/G
      collective-permute result             (one send per device)
    -done/"-start" pairs are counted once (the regex only accepts
    "-start" or the bare op before the open paren).
    """
    out: dict[str, dict] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        g = max(_group_size(line), 1)
        types = _TYPE_RE.findall(line[: m.start()])
        rbytes = sum(_tensor_bytes(d, s) for d, s in types)
        if kind == "all-gather":
            traffic = rbytes * (g - 1) / g
        elif kind == "reduce-scatter":
            traffic = float(rbytes * (g - 1))
        elif kind == "all-reduce":
            traffic = 2.0 * rbytes * (g - 1) / g
        elif kind == "all-to-all":
            traffic = rbytes * (g - 1) / g
        else:  # collective-permute
            traffic = float(rbytes)
        rec = out.setdefault(
            kind, {"count": 0, "result_bytes": 0, "traffic_bytes": 0.0}
        )
        rec["count"] += 1
        rec["result_bytes"] += rbytes
        rec["traffic_bytes"] += traffic
    return out


# ---------------------------------------------------------------------------
# FLOPs probe (scan bodies counted once -> probe unrolled small variants)
# ---------------------------------------------------------------------------


def _probe_variant(cfg: ModelConfig, n_layers: int) -> ModelConfig:
    # microbatches=1: the probe's reduced batch need not divide the
    # production microbatch count (flops are linear in batch anyway).
    kw = dict(n_layers=n_layers, scan_layers=False, remat="none",
              microbatches=1)
    if cfg.mamba is not None:
        # Single-chunk selective scan -> body counted exactly once.
        kw["mamba"] = cfg.mamba  # chunk handled below per shape
    return cfg.replace(**kw)


def flops_probe(cfg: ModelConfig, shape: ShapeConfig, kind: str) -> dict:
    """Per-unit HLO flops from unrolled 1-unit / 2-unit lowerings.

    Uses a reduced global batch (flops scale linearly; rescaled after)
    to keep probe compile time small.
    """
    p = cfg.pattern_len
    probe_batch = max(1, min(shape.global_batch, 4))
    scale = shape.global_batch / probe_batch
    pshape = ShapeConfig(shape.name, shape.seq_len, probe_batch, shape.kind)
    if cfg.mamba is not None:
        cfg = cfg.replace(
            mamba=cfg.mamba.__class__(
                d_state=cfg.mamba.d_state,
                d_conv=cfg.mamba.d_conv,
                expand=cfg.mamba.expand,
                dt_rank=cfg.mamba.dt_rank,
                scan_impl="chunked",
                chunk_size=pshape.seq_len if kind != "decode" else 128,
            )
        )

    def flops_for(n_layers: int) -> float:
        vcfg = _probe_variant(cfg, n_layers)
        fn, args, _, _, _ = _BUILDERS[kind](vcfg, pshape, None)
        lowered = jax.jit(fn).lower(*args)
        return float(lowered.compile().cost_analysis().get("flops", 0.0))

    f1 = flops_for(p)
    f2 = flops_for(2 * p)
    per_unit = max(f2 - f1, 0.0)
    n_units_eff = cfg.n_layers / p
    total = f1 + (n_units_eff - 1.0) * per_unit
    return {
        "probe_batch": probe_batch,
        "flops_1unit": f1,
        "flops_per_unit": per_unit,
        "hlo_flops_total": total * scale,
    }


# ---------------------------------------------------------------------------
# Analytic model FLOPs (roofline numerator)
# ---------------------------------------------------------------------------


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE); decode: D = new tokens."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        d = shape.global_batch * shape.seq_len
        return 6.0 * n * d
    if shape.kind == "prefill":
        d = shape.global_batch * shape.seq_len
        return 2.0 * n * d
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------


def validate_cell(
    arch: str | None, shape_name: str | None = None
) -> dict:
    """Name + analytic feasibility of one launch cell, no compile.

    The ``repro.sweep --dry-run`` hook: checks the arch/shape names
    against the registries and evaluates the analytic cost model
    (param counts, :func:`model_flops`) — everything :func:`run_cell`
    would record that doesn't require lowering or compiling. Raises
    ``ValueError`` with the known names on an unknown arch/shape.
    """
    if arch is not None and arch not in ARCH_IDS:
        raise ValueError(
            f"unknown arch {arch!r}; known: {sorted(ARCH_IDS)}"
        )
    if shape_name is not None and shape_name not in SHAPES:
        raise ValueError(
            f"unknown shape {shape_name!r}; known: {sorted(SHAPES)}"
        )
    if arch is None or shape_name is None:
        return {}
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    return {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "n_params": cfg.param_count(),
        "n_params_active": cfg.active_param_count(),
        "model_flops": model_flops(cfg, shape),
    }


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool,
    do_probe: bool = True,
    cim_mode: str | None = None,
    serve_quant: bool = False,
    kv_cache_dtype: str | None = None,
) -> dict:
    cfg = get_config(arch)
    if cim_mode:
        cfg = cfg.replace(cim=cfg.cim.__class__(mode=cim_mode))
    if kv_cache_dtype:
        cfg = cfg.replace(kv_cache_dtype=kv_cache_dtype)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    builder = _BUILDERS[shape.kind]
    if serve_quant:
        if shape.kind == "train":
            raise ValueError("--serve-quant applies to serving cells")
        import functools as _ft
        builder = _ft.partial(builder, serve_quant=True)
    rec: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "pod2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
        "cim_mode": cfg.cim.mode,
        "serve_quant": serve_quant,
        "n_params": cfg.param_count(),
        "n_params_active": cfg.active_param_count(),
    }
    t0 = time.time()  # noqa: CIM201 timing
    try:
        fn, args, in_sh, out_sh, jkw = builder(cfg, shape, mesh)
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             **jkw)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0  # noqa: CIM201 timing
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower  # noqa: CIM201 timing
            ma = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory={
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
            },
            cost={
                "flops_per_device": float(cost.get("flops", 0.0)),
                "bytes_accessed_per_device": float(
                    cost.get("bytes accessed", 0.0)
                ),
            },
            collectives=collective_inventory(hlo),
            model_flops=model_flops(cfg, shape),
        )
        if do_probe:
            try:
                rec["flops_probe"] = flops_probe(cfg, shape, shape.kind)
            except Exception as e:  # noqa: BLE001
                rec["flops_probe"] = {"error": repr(e)}
    except Exception as e:  # noqa: BLE001
        rec.update(status="fail", error=repr(e),
                   traceback=traceback.format_exc()[-4000:])
    rec["wall_s"] = round(time.time() - t0, 1)  # noqa: CIM201 timing
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-probe", action="store_true")
    ap.add_argument("--cim-mode", default=None)
    ap.add_argument("--serve-quant", action="store_true",
                    help="int8 weight-only serving params (W8A16)")
    ap.add_argument("--kv-cache-dtype", default=None,
                    help="KV cache storage dtype (e.g. float8_e4m3fn)")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument(
        "--skip-existing", action="store_true",
        help="skip cells already recorded ok in --out (crash-resume)",
    )
    args = ap.parse_args()

    cells: list[tuple[str, str]] = []
    if args.all:
        for arch in ARCH_IDS:
            for sh in shape_cells(arch):
                cells.append((arch, sh))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[
        args.mesh
    ]

    out_path = pathlib.Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    existing: dict[str, dict] = {}
    if out_path.exists():
        existing = json.loads(out_path.read_text())

    for arch, sh in cells:
        for mp in meshes:
            key = f"{arch}|{sh}|{'multi' if mp else 'single'}"
            if args.cim_mode:
                key += f"|{args.cim_mode}"
            if args.serve_quant:
                key += "|w8"
            if args.kv_cache_dtype:
                key += f"|kv-{args.kv_cache_dtype}"
            if (
                args.skip_existing
                and existing.get(key, {}).get("status") == "ok"
            ):
                print(f"[{key}] skip (existing ok)", flush=True)
                continue
            rec = run_cell(arch, sh, multi_pod=mp,
                           do_probe=not args.no_probe,
                           cim_mode=args.cim_mode,
                           serve_quant=args.serve_quant,
                           kv_cache_dtype=args.kv_cache_dtype)
            existing[key] = rec
            out_path.write_text(
                json.dumps(existing, indent=1, sort_keys=True)
            )
            status = rec["status"]
            mem = rec.get("memory", {})
            print(
                f"[{key}] {status} wall={rec['wall_s']}s "
                f"temp={mem.get('temp_bytes', 0)/2**30:.2f}GiB "
                f"args={mem.get('argument_bytes', 0)/2**30:.2f}GiB",
                flush=True,
            )


if __name__ == "__main__":
    main()
