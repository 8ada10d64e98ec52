"""Weight-stationary plan/execute CIM API and the backend registry.

The paper's macro is weight-stationary: 8-bit weights are written into
the P-8T SRAM arrays once and reused for every input vector. This module
makes that split explicit:

  plan_weights(w, cfg)        -> PlannedWeights   (once per weight)
  execute(x, plan, policy)    -> y                (per input batch)

``PlannedWeights`` is a jit-friendly pytree holding everything the
macro "stores": signed integer weight codes, optional bit-sliced planes,
the per-column code sums used for the digital zero-point correction,
and the per-output-channel dequantization scales. ``execute`` performs
only the per-input work (activation quantization, the integer macro
matmul, digital dequant) — none of the weight-side transforms are
repeated per call.

Execution backends are registered by string key:

  "fp"          plain floating-point matmul (framework baseline)
  "exact"       integer-exact quantized matmul (paper w/o ADC + noise)
  "behavioral"  full ADC/noise behavioral model (paper-faithful)
  "pallas"      same semantics via the Pallas GPQ kernel

The legacy mode names ('cim-exact', 'cim', 'cim-kernel') resolve to the
same backends, so a ``CIMPolicy.mode`` string is a valid backend key.
``register_backend`` lets deployments plug in alternatives (e.g. a
device-specific kernel) without touching the dispatch code.

A backend is ``fn(x2, plan, policy, key) -> y2`` over 2-D inputs; the
quantized built-ins share :func:`quantized_backend`, which wraps an
integer kernel ``(x_codes, plan, cfg, key) -> y_int`` with the common
activation-quantize / dequantize / zero-point epilogue.

``plan_params`` lifts planning over whole parameter pytrees (used by
``serve.quantized`` and ``ServeEngine``), unifying the CIM path and the
digital int8 weight-only serving path behind one representation.

One-shot entry points with straight-through gradients (QAT) remain
available as :func:`matmul` here and the backward-compatible
``core.matmul.cim_matmul`` shim.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Protocol

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core import matmul as matmul_lib
from repro.core.params import CIMConfig


class CIMPolicyLike(Protocol):
    """Structural type for repro.configs.base.CIMPolicy.

    Engine code is duck-typed against it to keep core free of config
    imports (configs.base already imports core.params).
    """

    mode: str
    cim: CIMConfig
    act_symmetric: bool
    act_clip_pct: float
    ste: bool
    backend: str


# ---------------------------------------------------------------------------
# PlannedWeights
# ---------------------------------------------------------------------------

@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("codes", "scale", "colsum", "w", "planes", "slots"),
    meta_fields=("weight_bits", "column_axis"),
)
@dataclasses.dataclass(frozen=True)
class PlannedWeights:
    """Persistent stored-weight state of one (stack of) linear layer(s).

    The macro analogue: ``codes``/``planes`` are what sits in the SRAM
    arrays, ``colsum``/``scale`` are the digital epilogue constants.

    Fields (all but ``codes``/``scale`` optional):
      codes:   [..., K, N] signed weight codes (int8 when weight_bits<=8).
      scale:   [..., 1, N] f32 per-output-channel dequant scale.
      colsum:  [..., 1, N] f32 per-column sum of codes (zero-point fix).
      w:       original full-precision weights, kept when the plan must
               also serve non-CIM (fp / digitally-exempt) matmuls.
      planes:  pre-grouped bit planes in the macro's row-group layout
               (zero-padded along K) so execute does no per-call
               weight-side reshaping. Two storage forms:
                 * unpacked [G, B, rows_active, N] int8 0/1 planes;
                 * packed   [G, rows_active, N] uint8 — 8 planes/byte
                   (bit b of each byte is plane b), chosen for large-K
                   layers where the unpacked form costs B extra bytes
                   per weight; the behavioral kernel unpacks one group
                   tile at a time inside its scan.
               Kept when the behavioral backend will run repeatedly on
               this plan.
      slots:   [G, rows_active, S*N] f32 spread-slot planes
               (``quant.spread_slots``): ``per_slot`` bit planes per
               f32 at an exact-integer stride, the operand of the
               decode-shape "slots" dispatch backend. Grouping is baked
               into the packed values, so unlike ``planes`` this form
               cannot be regrouped — a spec with a different
               ``rows_active`` simply doesn't use it.
      weight_bits: static weight precision (pytree metadata).
      column_axis: static; None except in the per-device view of a
               column-sharded plan (``distributed.sharding.per_device``),
               where ``codes``, ``w`` and ``planes`` hold this device's
               output columns while ``scale`` and ``colsum`` stay whole.
               It names the mesh axis the columns are split over.

    Every read of a plan yields whole columns, column shard or not: the
    macro path all-gathers its integer result before the epilogue
    (:meth:`whole_columns`), and :meth:`dequantized` /
    :meth:`best_weights` gather the weights. So all float work runs on
    whole rows, as on one device.
    """

    codes: Any
    scale: Any
    colsum: Any = None
    w: Any = None
    planes: Any = None
    slots: Any = None
    weight_bits: int = 8
    column_axis: str | None = None

    # -- convenience views -------------------------------------------------

    @property
    def k(self) -> int:
        return self.codes.shape[-2]

    @property
    def n(self) -> int:
        """Output width; whole even when ``codes`` is a column shard."""
        return self.scale.shape[-1]

    @property
    def codes_i32(self) -> jax.Array:
        c = self.codes
        return c if c.dtype == jnp.int32 else c.astype(jnp.int32)

    def whole_columns(self, y: jax.Array) -> jax.Array:
        """[..., n] from a result over this plan's own columns: the
        identity, except in a column shard, which all-gathers them."""
        if self.column_axis is None:
            return y
        return jax.lax.all_gather(y, self.column_axis, axis=y.ndim - 1,
                                  tiled=True)

    def dequantized(self, dtype=jnp.float32) -> jax.Array:
        """w ~= scale * codes (the digital int8 serving read path)."""
        codes = self.whole_columns(self.codes)
        return codes.astype(dtype) * self.scale.astype(dtype)

    def best_weights(self, dtype=jnp.float32) -> jax.Array:
        """Full-precision weights if kept, else the dequantized codes."""
        if self.w is not None:
            return self.whole_columns(self.w).astype(dtype)
        return self.dequantized(dtype)


# Above this reduction depth the behavioral planes are stored bit-packed
# (8 planes/byte): at K = 4096 the unpacked [G, B, rows, N] int8 form is
# weight_bits x the codes themselves, which dominates plan storage for
# the large-K layers (MLP down-projections, im2col stacks).
PACK_PLANES_MIN_K = 4096


def _pack_planes_default(k: int, cfg: CIMConfig) -> bool:
    return k >= PACK_PLANES_MIN_K and cfg.weight_bits <= 8


# Spread-slot operands default on up to this many weights per layer:
# the form costs 4 * n_slots (typically 12) bytes per weight, so it is
# built for the decode-critical attention/projection layers and skipped
# for the very largest matrices unless explicitly requested.
SLOTS_MAX_ELEMS = 1 << 22


def _with_slots_default(
    k: int, n: int, cfg: CIMConfig, with_planes: bool,
    rows: int | None = None,
) -> bool:
    return (
        with_planes
        and k * n <= SLOTS_MAX_ELEMS
        and quant.slot_spec(
            rows or cfg.rows_active, cfg.act_bits, cfg.weight_bits
        ) is not None
    )


def _slots_shape(
    k: int, n: int, cfg: CIMConfig, rows: int | None = None
) -> tuple[int, int, int]:
    rows = rows or cfg.rows_active
    ss = quant.slot_spec(rows, cfg.act_bits, cfg.weight_bits)
    return (-(-k // rows), rows, ss.n_slots * n)


def _grouped_planes_shape(
    k: int, n: int, cfg: CIMConfig, packed: bool = False,
    rows: int | None = None,
) -> tuple[int, ...]:
    rows = rows or cfg.rows_active
    if packed:
        return (-(-k // rows), rows, n)
    return (-(-k // rows), cfg.weight_bits, rows, n)


def _grouped_planes(
    codes: jax.Array, cfg: CIMConfig, packed: bool = False,
    rows: int | None = None,
) -> jax.Array:
    """[K, N] signed codes -> grouped bit planes.

    The macro's row-group layout: group g holds rows g*rows..(g+1)*rows
    of every bit plane, zero-padded along K (bit planes of code 0 are
    all 0, so padding is neutral — tested in test_cim_matmul).

    packed=False: [G, B, rows, N] int8 0/1 planes.
    packed=True:  [G, rows, N] uint8 with 8 planes/byte — bit b of each
    byte is plane b, i.e. the low ``weight_bits`` two's-complement bits
    of the code; the behavioral kernel bit-slices one [rows, N] tile per
    scan step, so peak memory never sees the unpacked tensor.

    ``rows`` overrides the grouping row count (a layer's *calibrated*
    ``rows_active`` may differ from the plan cfg's — grouping at it up
    front makes the analog backend's regroup a no-op).
    """
    k, n = codes.shape
    rows = rows or cfg.rows_active
    g = -(-k // rows)
    if packed:
        if cfg.weight_bits > 8:
            raise ValueError(
                f"pack_planes requires weight_bits <= 8 (one byte per "
                f"weight); got {cfg.weight_bits}"
            )
        mask = (1 << cfg.weight_bits) - 1
        u = jnp.bitwise_and(codes.astype(jnp.int32), mask).astype(jnp.uint8)
        u = jnp.pad(u, ((0, g * rows - k), (0, 0)))
        return u.reshape(g, rows, n)
    b = cfg.weight_bits
    p = quant.bitslice_weights(codes, b, dtype=jnp.int8)  # [B, K, N]
    p = jnp.pad(p, ((0, 0), (0, g * rows - k), (0, 0)))
    return p.reshape(b, g, rows, n).transpose(1, 0, 2, 3)


def regroup_planes(
    planes: jax.Array, k: int, to_rows: int
) -> jax.Array:
    """Regroup planned bit planes to a different ``rows_active``.

    Plans group their planes at plan-time ``cfg.rows_active``; a
    calibrated backend may select a different row count per layer.
    Rather than dropping the planes (falling back to per-call bit
    slicing — the exact regression this guards against), the grouped
    layout is reflowed: ungroup along K, trim the old zero padding,
    re-pad and re-group at ``to_rows``. Works for both storage forms
    (unpacked [G, B, rows, N] int8 and packed [G, rows, N] uint8) and
    is pure reshape/pad, so it fuses into the surrounding jit.
    """
    g2 = -(-k // to_rows)
    if planes.ndim == 3:  # packed, 8 planes/byte
        g, rows, n = planes.shape
        flat = planes.reshape(g * rows, n)[:k]
        flat = jnp.pad(flat, ((0, g2 * to_rows - k), (0, 0)))
        return flat.reshape(g2, to_rows, n)
    g, b, rows, n = planes.shape
    flat = planes.transpose(1, 0, 2, 3).reshape(b, g * rows, n)[:, :k]
    flat = jnp.pad(flat, ((0, 0), (0, g2 * to_rows - k), (0, 0)))
    return flat.reshape(b, g2, to_rows, n).transpose(1, 0, 2, 3)


def plan_weights(
    w: jax.Array,
    cfg: CIMConfig | None = None,
    policy: CIMPolicyLike | None = None,
    *,
    keep_fp: bool | None = None,
    with_planes: bool | None = None,
    pack_planes: bool | None = None,
    with_slots: bool | None = None,
    group_rows: int | None = None,
) -> PlannedWeights:
    """Precompute the weight-stationary state for ``execute``.

    All weight-side transforms of the old per-call path happen here,
    once: symmetric per-channel quantization, per-column code sums, and
    (optionally) two's-complement bit-slicing.

    Args:
      w: [..., K, N] float weights (last axis = output channels).
      cfg: macro operating point; defaults to ``policy.cim`` or the
        paper operating point.
      policy: optional CIMPolicy; sets defaults for the knobs below.
      keep_fp: retain the original float weights in the plan (needed
        for bit-exact 'fp'/digitally-exempt execution). Default True;
        pass False for the storage-saving digital int8 serving form
        (plan_params' 'fp'-policy default).
      with_planes: precompute the bit-sliced planes (saves per-call
        slicing in the behavioral backend). Default: only when the
        policy's mode is the behavioral model.
      pack_planes: store the planes bit-packed 8/byte ([G, rows, N]
        uint8, unpacked tile-by-tile inside the behavioral kernel)
        instead of unpacked [G, B, rows, N] int8. Default: packed for
        large-K layers (K >= PACK_PLANES_MIN_K). Execution output is
        identical either way (parity-tested).
      with_slots: also precompute the spread-slot operand
        (``quant.spread_slots``) consumed by the decode-shape "slots"
        dispatch backend. Default: whenever planes are kept, the
        packing is feasible at the operating point, and the layer has
        at most SLOTS_MAX_ELEMS weights (the form costs ~12 bytes per
        weight). Pass True/False to force.
      group_rows: group the planes at this row count instead of
        ``cfg.rows_active`` — used by ``plan_params(calibration=...)``
        to pre-group each layer at its *calibrated* ``rows_active`` so
        the analog backend's ``regroup_planes`` reshape never runs.
    """
    if cfg is None:
        cfg = policy.cim if policy is not None else CIMConfig()
    mode = policy.mode if policy is not None else None
    if keep_fp is None:
        keep_fp = True
    if with_planes is None:
        with_planes = mode in ("cim", "behavioral")

    bits = cfg.weight_bits
    # Quantize in f32 regardless of the storage dtype of w (a bf16
    # amax/scale would perturb the codes; no-op for f32 params).
    qw = quant.quantize_weights(w.astype(jnp.float32), bits)
    codes = qw.codes.astype(cfg.codes_dtype)
    colsum = jnp.sum(qw.codes, axis=-2, keepdims=True).astype(jnp.float32)
    planes = None
    if with_planes:
        if qw.codes.ndim != 2:
            raise ValueError(
                "with_planes requires a 2-D [K, N] weight; got shape "
                f"{qw.codes.shape}"
            )
        if pack_planes is None:
            pack_planes = _pack_planes_default(qw.codes.shape[0], cfg)
        planes = _grouped_planes(
            qw.codes, cfg, packed=pack_planes, rows=group_rows
        )
    slots = None
    if with_slots is None:
        with_slots = qw.codes.ndim == 2 and _with_slots_default(
            qw.codes.shape[-2], qw.codes.shape[-1], cfg, with_planes,
            rows=group_rows,
        )
    if with_slots:
        if qw.codes.ndim != 2:
            raise ValueError(
                "with_slots requires a 2-D [K, N] weight; got shape "
                f"{qw.codes.shape}"
            )
        slots = quant.spread_slots(
            qw.codes, group_rows or cfg.rows_active,
            cfg.act_bits, bits,
        )
    return PlannedWeights(
        codes=codes,
        scale=qw.scale.astype(jnp.float32),
        colsum=colsum,
        w=w if keep_fp else None,
        planes=planes,
        slots=slots,
        weight_bits=bits,
    )


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

# fn(x2 [M, K] float, plan, policy, key) -> y2 [M, N] float
BackendFn = Callable[
    [jax.Array, PlannedWeights, CIMPolicyLike, jax.Array | None], jax.Array
]

_BACKENDS: dict[str, BackendFn] = {}

# Legacy CIMPolicy.mode strings -> canonical backend keys.
_MODE_ALIASES = {
    "cim-exact": "exact",
    "cim": "behavioral",
    "cim-kernel": "pallas",
}


def register_backend(
    name: str, fn: BackendFn, *, overwrite: bool = False
) -> None:
    """Register an execution backend under a string key."""
    if name in _MODE_ALIASES:
        raise ValueError(
            f"'{name}' is a reserved mode alias for "
            f"'{_MODE_ALIASES[name]}'; register under the canonical key"
        )
    if name in _BACKENDS and not overwrite:
        raise ValueError(
            f"backend '{name}' already registered (overwrite=True to "
            "replace)"
        )
    _BACKENDS[name] = fn


def get_backend(name: str) -> BackendFn:
    """Resolve a backend key (canonical name or legacy mode alias)."""
    key = _MODE_ALIASES.get(name, name)
    try:
        return _BACKENDS[key]
    except KeyError:
        raise KeyError(
            f"unknown CIM backend '{name}'; registered: "
            f"{sorted(_BACKENDS)}"
        ) from None


def backend_names() -> tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


def quantized_backend(int_fn) -> BackendFn:
    """Wrap ``int_fn(x_codes, plan, cfg, key) -> y_int`` with the shared
    quantized-execution epilogue (the digital periphery of the macro):
    dynamic activation quantization in, dequantization + zero-point
    column correction out."""

    def run(x2, plan, policy, key):
        cfg = policy.cim
        qa = quant.quantize_acts(
            x2,
            cfg.act_bits,
            symmetric=policy.act_symmetric,
            clip_pct=policy.act_clip_pct,
        )
        y_int = plan.whole_columns(int_fn(qa.codes, plan, cfg, key))
        colsum = plan.colsum
        if colsum is None:  # minimal plans: recover digitally (free)
            colsum = plan.whole_columns(jnp.sum(
                plan.codes_i32, axis=-2, keepdims=True
            ).astype(jnp.float32))
        y = y_int - qa.zero_point.astype(jnp.float32) * colsum
        return y * qa.scale * plan.scale

    return run


def _fp_backend(x2, plan, policy, key):
    del policy, key
    return x2 @ plan.best_weights(x2.dtype)


def _exact_int(x_codes, plan, cfg, key):
    del cfg, key
    return matmul_lib.cim_matmul_exact_int(x_codes, plan.codes_i32)


def _behavioral_int(x_codes, plan, cfg, key):
    # Route through the variant-aware dispatch table: the backend
    # (scan / ref / slots / pallas) and its block sizes resolve per
    # shape from the autotune cache, falling back to the heuristics
    # (noise -> the scan transfer; otherwise scan off-TPU). Planned
    # operands pass through untouched — dispatch normalizes grouping
    # only when the chosen implementation actually consumes them, so
    # nothing weight-side runs on the hot path.
    from repro.kernels import dispatch  # lazy: optional pallas dep

    return dispatch.dispatch(
        x_codes, plan.codes, cfg, key=key, planes=plan.planes,
        slots=plan.slots,
    )


def _pallas_int(x_codes, plan, cfg, key):
    del key  # kernel is noiseless by design (production inference path)
    from repro.kernels import dispatch  # lazy: optional dep

    return dispatch.dispatch(
        x_codes, plan.codes, cfg, backend="pallas", planes=plan.planes
    )


# The built-in execution backends (registered below). Serving-time
# calibration auto-registration must never overwrite these or their
# legacy mode aliases.
BUILTIN_BACKENDS = frozenset({"fp", "exact", "behavioral", "pallas"})


def is_builtin_backend(name: str) -> bool:
    return name in BUILTIN_BACKENDS or name in _MODE_ALIASES


register_backend("fp", _fp_backend)
register_backend("exact", quantized_backend(_exact_int))
register_backend("behavioral", quantized_backend(_behavioral_int))
register_backend("pallas", quantized_backend(_pallas_int))


# ---------------------------------------------------------------------------
# execute / one-shot matmul
# ---------------------------------------------------------------------------


def execute(
    x: jax.Array,
    plan: PlannedWeights,
    policy: CIMPolicyLike,
    *,
    key: jax.Array | None = None,
) -> jax.Array:
    """Run one input batch against a precomputed weight plan.

    The backend is ``policy.backend`` when set, else derived from
    ``policy.mode`` through the registry aliases. Inputs of any rank
    are flattened to [M, K] and restored afterwards.
    """
    name = getattr(policy, "backend", "") or policy.mode
    fn = get_backend(name)
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1])
    y = fn(x2, plan, policy, key)
    y = y.reshape(*orig_shape[:-1], plan.n)
    if policy.mode != "fp":
        y = y.astype(x.dtype)
    return y


def _plan_and_execute(x, w, policy, key):
    plan = plan_weights(w, policy=policy)
    return execute(x, plan, policy, key=key)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _matmul_ste(x, w, policy, key):
    return _plan_and_execute(x, w, policy, key)


def _matmul_ste_fwd(x, w, policy, key):
    return _plan_and_execute(x, w, policy, key), (x, w)


def _matmul_ste_bwd(policy, res, g):
    # Straight-through: backward is the underlying linear map
    # (d/dx = w^T, d/dw = x^T), the QAT estimator the paper's own
    # system simulation implies.
    x, w = res
    k = x.shape[-1]
    g2 = g.reshape(-1, g.shape[-1])
    x2 = x.reshape(-1, k)
    dx = (g2 @ w.T).reshape(x.shape).astype(x.dtype)
    dw = (x2.T @ g2).astype(w.dtype)
    return dx, dw, None


_matmul_ste.defvjp(_matmul_ste_fwd, _matmul_ste_bwd)


def matmul(
    x: jax.Array,
    w: jax.Array,
    policy: CIMPolicyLike | None,
    *,
    key: jax.Array | None = None,
) -> jax.Array:
    """One-shot plan+execute for weights that change every step (QAT).

    Training can't reuse a plan across steps, so this is the
    gradient-capable entry point: forward runs the full planned path,
    backward is the straight-through estimator when ``policy.ste``.
    """
    if policy is None or policy.mode == "fp":
        return x @ w
    if getattr(policy, "ste", True):
        return _matmul_ste(x, w, policy, key)
    return _plan_and_execute(x, w, policy, key)


# ---------------------------------------------------------------------------
# Whole-pytree planning (serving)
# ---------------------------------------------------------------------------

# Leaves that must never be weight-planned (mirrors serve.quantized).
DEFAULT_EXEMPT_KEYS = frozenset(
    {"scale", "bias", "b", "table", "a_log", "d_skip", "conv_w",
     "conv_b", "mu_x", "decay_w0", "bonus_u", "pos_emb"}
)
# Modules kept high-precision by design: the MoE router (routing
# decisions are precision-critical) and the tiny shared-expert gate.
DEFAULT_EXEMPT_MODULES = frozenset({"router", "shared_gate"})
# Keys carrying matmul weight leaves ([K, N] linears, [E, K, N] banks).
DEFAULT_WEIGHT_KEYS = frozenset({"w", "gate", "up", "down"})
_PLAN_MIN_DIM = 2


def _plan_sds_leaf(
    v, cfg: CIMConfig, keep_fp: bool, with_planes: bool,
    group_rows: int | None = None,
) -> PlannedWeights:
    """Shape/dtype stand-in plan for dry-run (ShapeDtypeStruct) trees.

    Must mirror plan_weights field-for-field (same Nones) so dry-run and
    concrete planned trees share one pytree structure.
    """
    epi = v.shape[:-2] + (1,) + v.shape[-1:]
    planes = None
    if with_planes:
        packed = _pack_planes_default(v.shape[-2], cfg)
        planes = jax.ShapeDtypeStruct(
            _grouped_planes_shape(
                v.shape[-2], v.shape[-1], cfg, packed, rows=group_rows
            ),
            jnp.uint8 if packed else jnp.int8,
        )
    slots = None
    if len(v.shape) == 2 and _with_slots_default(
        v.shape[-2], v.shape[-1], cfg, with_planes, rows=group_rows
    ):
        slots = jax.ShapeDtypeStruct(
            _slots_shape(v.shape[-2], v.shape[-1], cfg, rows=group_rows),
            jnp.float32,
        )
    return PlannedWeights(
        codes=jax.ShapeDtypeStruct(v.shape, cfg.codes_dtype),
        scale=jax.ShapeDtypeStruct(epi, jnp.float32),
        colsum=jax.ShapeDtypeStruct(epi, jnp.float32),
        w=jax.ShapeDtypeStruct(v.shape, v.dtype) if keep_fp else None,
        planes=planes,
        slots=slots,
        weight_bits=cfg.weight_bits,
    )


def plan_params(
    params: Any,
    cfg: CIMConfig | None = None,
    policy: CIMPolicyLike | None = None,
    *,
    keep_fp: bool | None = None,
    with_planes: bool | None = None,
    calibration: Any | None = None,
    weight_keys: frozenset[str] = DEFAULT_WEIGHT_KEYS,
    exempt_keys: frozenset[str] = DEFAULT_EXEMPT_KEYS,
    exempt_modules: frozenset[str] = DEFAULT_EXEMPT_MODULES,
) -> Any:
    """Rewrite every eligible weight leaf into a PlannedWeights.

    One transform serves both serving representations:
      * digital int8 weight-only (policy None / mode 'fp'): plans drop
        the float weights, halving/quartering HBM weight traffic — the
        TPU analogue of the macro's resident 8-bit SRAM weights;
      * CIM execution (other modes): plans keep the float weights so
        digitally-exempt matmuls stay bit-identical, and the CIM
        layers reuse codes/colsums/planes across every decode step.

    ``calibration`` (a ``core.calibrate.CalibrationResult``; duck-typed
    to keep the import DAG one-way) pre-groups each layer's planes at
    its *calibrated* ``rows_active``, looked up by [K, N] shape — the
    calibrated backend then consumes every plan as-is instead of
    tracing the one-off ``regroup_planes`` reshape on first execute.

    Works on concrete arrays AND ShapeDtypeStruct trees (dry-run).
    Embeddings/norms/etc. (``exempt_keys``/``exempt_modules``) pass
    through untouched.
    """
    if cfg is None:
        cfg = policy.cim if policy is not None else CIMConfig()
    mode = policy.mode if policy is not None else "fp"
    if keep_fp is None:
        keep_fp = mode != "fp"
    if with_planes is None:
        with_planes = mode in ("cim", "behavioral") or calibration is not None

    def eligible(k, v):
        return (
            k in weight_keys
            and k not in exempt_keys
            and hasattr(v, "ndim")
            and v.ndim >= _PLAN_MIN_DIM
        )

    def rows_for(shape) -> int | None:
        if calibration is None or len(shape) != 2:
            return None
        lc = calibration.layer_for(shape[-2], shape[-1])
        return None if lc is None else lc.spec.rows_active

    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            if isinstance(v, dict):
                out[k] = v if k in exempt_modules else walk(v)
            elif not eligible(k, v):
                out[k] = v
            elif isinstance(v, jax.ShapeDtypeStruct):
                out[k] = _plan_sds_leaf(
                    v, cfg, keep_fp,
                    with_planes and len(v.shape) == 2,
                    group_rows=rows_for(v.shape),
                )
            else:
                out[k] = plan_weights(
                    v, cfg, policy, keep_fp=keep_fp,
                    with_planes=with_planes and v.ndim == 2,
                    group_rows=rows_for(v.shape),
                )
        return out

    return walk(params)


def planned_axes(
    axes: Any,
    *,
    keep_fp: bool = False,
    weight_keys: frozenset[str] = DEFAULT_WEIGHT_KEYS,
    exempt_modules: frozenset[str] = DEFAULT_EXEMPT_MODULES,
) -> Any:
    """Transform a logical-axes tree to match ``plan_params`` output.

    Codes (and kept fp weights) inherit the weight's axes; the [..1, N]
    epilogue vectors (scale, colsum) keep only the out-channel axis.
    """

    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            if isinstance(v, dict):
                out[k] = v if k in exempt_modules else walk(v)
            elif (
                k in weight_keys
                and isinstance(v, tuple)
                and len(v) >= _PLAN_MIN_DIM
            ):
                epi = v[:-2] + (None,) + v[-1:]
                out[k] = PlannedWeights(
                    codes=v,
                    scale=epi,
                    colsum=epi,
                    w=v if keep_fp else None,
                    planes=None,
                )
            else:
                out[k] = v
        return out

    return walk(axes)
