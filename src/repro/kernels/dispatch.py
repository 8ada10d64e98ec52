"""Unified variant-aware kernel dispatch: one table for every macro matmul.

Before this module, executing a macro variant took three parallel
edits: a tuned backend in ``kernels/ops.py``, a string key in
``core/engine.py``'s backend registry, and a per-variant ``matmul_int``
wired into ``core/variants.py`` / ``core/calibrate.py``. The dispatch
table collapses them into one subsystem: a

    KernelKey(variant, backend, shape_cell, dtype) -> implementation

map that ``engine.execute`` (the behavioral/pallas built-ins), the
calibrated "analog" backend and ``ServeEngine`` all route through.
Adding a macro variant or a device kernel is ONE ``register_kernel``
call (and any variant registered in ``core.variants`` gets its scan
transfer auto-wired — zero calls).

Built-in backends per variant:

  "scan"    the jnp ``lax.scan`` transfer (one group per step). The
            only backend that injects hardware noise; peak memory is
            one group tile, so it is the large-shape default.
  "ref"     the vectorized formulation (kernels.ref): a single fused
            einsum pair. Wins at decode shapes (small M) on CPU/GPU —
            the per-shape choice the autotuner discovers.
  "slots"   the spread-slot formulation (kernels.ref): all bit planes
            packed into exact f32 integer fields so ONE batched dot
            yields every plane pMAC. Needs the plan's precomputed
            ``slots`` operand (grouped at the executing rows_active —
            it cannot be regrouped); the decode-shape (small M)
            bandwidth winner.
  "pallas"  the fused Pallas kernel (kernels.cim_mac); native lowering
            on TPU, interpret mode elsewhere. Noiseless by design
            (production inference path). Consumes a plan's *packed*
            planes directly (flatten-sliced to the [K, N] byte matrix,
            unpacked per tile inside the kernel).

Resolution order when no backend is requested explicitly:

  1. hardware-noise injection (``spec.noisy`` and a key) semantically
     requires the scan transfer — recorded as source="noise";
  2. the autotune cache (``kernels.autotune``): the pinned winner for
     (arch, variant, shape cell), including its block sizes;
  3. heuristics: the variant's Pallas kernel on TPU, else the scan.

An explicit ``backend=`` request is always honored (no silent
fallback — ``record_resolutions`` lets callers and the check.sh guard
assert exactly which implementation ran); an unknown key raises. An
implicitly chosen implementation that raises ``KernelInfeasible`` (its
own depth or operand guard) falls back to the scan, recorded as
source="guard-fallback"; every other error propagates, so a kernel the
device compiler refuses is never hidden behind the scan.

An implementation is ``fn(x_codes, w_codes, spec, *, key=None,
planes=None, block=None) -> [M, N] float32`` in integer-domain macro
units — the ``matmul.cim_matmul_int`` contract (plus ``slots=`` for
implementations registered with ``supports_slots``). ``planes``
carries a plan's pre-grouped bit planes (packed planes feed the Pallas
kernels directly; the dispatcher regroups mismatched planes only for
implementations that read them), ``slots`` a plan's spread-slot
operand, ``block`` a (bm, bn, bk) Pallas tiling.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Iterator

import jax

from repro.core import matmul as matmul_lib
from repro.core import variants as variants_lib
from repro.core.params import CIMConfig
from repro.core.pipeline import MacroSpec, as_spec
from repro.kernels import ref as ref_lib
from repro.kernels.ref import KernelInfeasible

# fn(x_codes, w_codes, spec, *, key, planes, block) -> [M, N] f32
KernelFn = Callable[..., jax.Array]

# Backend preference order (used by autotune candidate enumeration).
KNOWN_BACKENDS = ("scan", "ref", "slots", "pallas")


@dataclasses.dataclass(frozen=True)
class KernelKey:
    """Registration/lookup key of one kernel implementation.

    ``shape_cell``/``dtype`` of None are wildcards (match any); a
    non-None cell or dtype registers a shape- or dtype-specialized
    kernel that wins over the generic one (most-specific-first lookup).
    """

    variant: str
    backend: str
    shape_cell: tuple[int, int, int] | None = None
    dtype: str | None = None


@dataclasses.dataclass(frozen=True)
class KernelImpl:
    """A registered implementation plus its capability flags."""

    fn: KernelFn
    supports_noise: bool = False
    supports_planes: bool = False
    supports_slots: bool = False
    is_pallas: bool = False


@dataclasses.dataclass(frozen=True)
class Resolution:
    """One dispatch decision (recorded at trace time under jit)."""

    key: KernelKey
    source: str  # "explicit" | "noise" | "tuned" | "heuristic"
    block: tuple[int, int, int] | None = None


_TABLE: dict[KernelKey, KernelImpl] = {}
_LISTENERS: list[Callable[[Resolution], None]] = []


def register_kernel(
    key: KernelKey,
    fn: KernelFn,
    *,
    supports_noise: bool = False,
    supports_planes: bool = False,
    supports_slots: bool = False,
    is_pallas: bool = False,
    overwrite: bool = False,
) -> KernelKey:
    """Register one implementation under a KernelKey. Returns the key."""
    if key in _TABLE and not overwrite:
        raise ValueError(
            f"kernel {key} already registered (overwrite=True to replace)"
        )
    _TABLE[key] = KernelImpl(
        fn=fn,
        supports_noise=supports_noise,
        supports_planes=supports_planes,
        supports_slots=supports_slots,
        is_pallas=is_pallas,
    )
    return key


def kernel_keys() -> tuple[KernelKey, ...]:
    """Every registered key, deterministically ordered."""
    return tuple(sorted(
        _TABLE,
        key=lambda k: (k.variant, k.backend, k.shape_cell or (),
                       k.dtype or ""),
    ))


def backends_for(variant: str) -> tuple[str, ...]:
    """Registered backends of one variant, in preference order."""
    got = {k.backend for k in _TABLE if k.variant == variant}
    if variant in variants_lib.names():
        got.add("scan")  # auto-wired from the MacroVariant registry
    ordered = [b for b in KNOWN_BACKENDS if b in got]
    return tuple(ordered + sorted(got - set(KNOWN_BACKENDS)))


def has_pallas(variant: str) -> bool:
    return any(
        k.variant == variant and _TABLE[k].is_pallas for k in _TABLE
    )


_CELL_CAP = 8192


def shape_cell(m: int, k: int, n: int) -> tuple[int, int, int]:
    """Bucket a concrete (M, K, N) into its tuning cell.

    Each dim rounds up to the next power of two (capped at 8192): the
    autotuner sweeps one representative per cell and the pinned winner
    serves every shape in it — decode steps with 1..8 in-flight tokens
    all land in the m=8 cell, for example.
    """

    def cell(d: int) -> int:
        p = 1
        while p < d and p < _CELL_CAP:
            p *= 2
        return p

    return (cell(m), cell(k), cell(n))


def lookup(
    variant: str,
    backend: str,
    shape_cell: tuple[int, int, int] | None = None,
    dtype: str | None = None,
) -> KernelImpl | None:
    """Most-specific-first table lookup; auto-wires variant scans.

    A "scan" miss for a variant present in the ``core.variants``
    registry is satisfied from ``MacroVariant.matmul_int``, so
    registering a variant is enough to execute it — the dispatch half
    of "one registration instead of three edits". (The auto-wired impl
    is built per lookup, NOT written into the table: a later explicit
    ``register_kernel(KernelKey(v, "scan"), ...)`` must succeed
    regardless of whether a dispatch ran first.)
    """
    for key in (
        KernelKey(variant, backend, shape_cell, dtype),
        KernelKey(variant, backend, shape_cell, None),
        KernelKey(variant, backend, None, dtype),
        KernelKey(variant, backend, None, None),
    ):
        impl = _TABLE.get(key)
        if impl is not None:
            return impl
    if backend == "scan" and variant in variants_lib.names():
        var = variants_lib.get(variant)

        def run(x_codes, w_codes, spec, *, key=None, planes=None,
                block=None, _fn=var.matmul_int):
            del block
            return _fn(x_codes, w_codes, spec, key=key, planes=planes)

        return KernelImpl(
            fn=run, supports_noise=True, supports_planes=True
        )
    return None


@contextlib.contextmanager
def record_resolutions() -> Iterator[list[Resolution]]:
    """Capture every dispatch decision made inside the context.

    Under jit the decision happens at trace time, so a cached
    compilation records nothing — wrap the first (tracing) call. Used
    by the no-silent-fallback guard in benchmarks/kernel_bench.py and
    the routing tests.
    """
    log: list[Resolution] = []
    _LISTENERS.append(log.append)
    try:
        yield log
    finally:
        _LISTENERS.remove(log.append)


def _notify(res: Resolution) -> None:
    for cb in _LISTENERS:
        cb(res)


def _has_backend(variant: str, backend: str) -> bool:
    return any(
        k.variant == variant and k.backend == backend for k in _TABLE
    )


# Largest M for which the heuristic (no tuned pin) takes the slots
# formulation: its weight traffic is M-independent, so it wins the
# bandwidth-bound decode shapes and loses to plain contractions once M
# amortizes the weight reads. The autotune corpus overrides per cell.
_SLOTS_HEURISTIC_MAX_M = 32


def _heuristic_backend(variant: str, planes, slots, m: int) -> str:
    # A plan's spread-slot operand exists exactly for the decode shapes
    # — take it when M is small and no tuned pin says otherwise.
    if (
        slots is not None
        and m <= _SLOTS_HEURISTIC_MAX_M
        and _has_backend(variant, "slots")
    ):
        return "slots"
    # Unpacked pre-grouped planes are a weight-stationary optimization
    # the Pallas kernels don't consume (packed planes they do, via the
    # flatten-slice path) — implicit routing keeps the plan semantics
    # and takes the scan; the autotune cache can still pin pallas.
    if (
        (planes is None or planes.ndim == 3)
        and jax.default_backend() == "tpu"
        and has_pallas(variant)
    ):
        return "pallas"
    return "scan"


def dispatch(
    x_codes: jax.Array,
    w_codes: jax.Array,
    spec: CIMConfig | MacroSpec,
    *,
    variant: str = "p8t",
    backend: str | None = None,
    key: jax.Array | None = None,
    planes: jax.Array | None = None,
    slots: jax.Array | None = None,
    block: tuple[int, int, int] | None = None,
) -> jax.Array:
    """Route one integer-domain macro matmul to its implementation.

    Args:
      x_codes: [M, K] activation codes; w_codes: [K, N] signed weight
        codes (a plan's ``codes`` — any integer dtype).
      spec: the operating point (variant transfer constants).
      variant: macro family name (``core.variants`` registry).
      backend: explicit implementation choice; None = tuned/heuristic.
      key: PRNG key for hardware-noise injection — routes to the scan
        transfer unless the backend was requested explicitly (the
        Pallas/ref formulations are noiseless by design and ignore it).
      planes: plan-grouped bit planes. Forwarded to implementations
        that consume them; a grouping mismatch with the executing
        ``spec.rows_active`` is normalized here (regroup) at trace
        time, ONLY when the chosen implementation actually reads them
        — nothing weight-side runs for kernels that ignore planes.
      slots: plan spread-slot operand (``plan_weights(with_slots=)``).
        Dropped when grouped at a different rows_active (slots cannot
        be regrouped); the "slots" backend requires it.
      block: (bm, bn, bk) Pallas tiling override; defaults to the
        tuned winner's blocks, else (128, 128, 128).
    """
    spec = as_spec(spec)
    m, k = x_codes.shape
    n = w_codes.shape[-1]
    cell = shape_cell(m, k, n)
    dtype = w_codes.dtype.name
    noisy = bool(spec.noisy) and key is not None
    if slots is not None and slots.shape[-2] != spec.rows_active:
        # Grouped for a different row count — the slot fields encode
        # that grouping irreversibly, so the operand is unusable here.
        slots = None

    source = "explicit"
    if backend is None:
        if noisy:
            backend, source = "scan", "noise"
        else:
            from repro.kernels import autotune  # noqa: PLC0415 - cycle-free lazy

            win = autotune.lookup(variant, cell)
            if win is not None:
                backend, source = win.backend, "tuned"
                if block is None:
                    block = win.block
            else:
                backend = _heuristic_backend(variant, planes, slots, m)
                source = "heuristic"

    impl = lookup(variant, backend, cell, dtype)
    if impl is None:
        raise KeyError(
            f"no kernel registered for variant='{variant}' "
            f"backend='{backend}' (cell={cell}, dtype={dtype}); "
            f"registered backends for this variant: "
            f"{backends_for(variant)}"
        )
    _notify(Resolution(
        key=KernelKey(variant, backend, cell, dtype),
        source=source,
        block=block if impl.is_pallas else None,
    ))

    def planes_for(chosen: KernelImpl):
        if not chosen.supports_planes or planes is None:
            return None
        if chosen.is_pallas or planes.shape[-2] == spec.rows_active:
            # The Pallas flatten-slice path recovers the [K, N] byte
            # matrix at ANY grouping — no regroup needed there.
            return planes
        from repro.core import engine  # noqa: PLC0415 - lazy, no cycle

        return engine.regroup_planes(planes, k, spec.rows_active)

    def run(chosen: KernelImpl, name: str, blk):
        kwargs: dict[str, Any] = dict(
            key=key if chosen.supports_noise else None,
            planes=planes_for(chosen),
            block=blk,
        )
        if chosen.supports_slots:
            kwargs["slots"] = slots
        # The backend that ran names the device scope of its operations
        # (``cim.macro/scan`` inside the engine's macro scope).
        with jax.named_scope(name):
            return chosen.fn(x_codes, w_codes, spec, **kwargs)

    if source == "explicit" or backend == "scan":
        return run(impl, backend, block)
    try:
        return run(impl, backend, block)
    except KernelInfeasible:
        # Implicitly-chosen impl infeasible at this shape/operating
        # point (e.g. the Pallas f32 depth guard, a stale tuned pin):
        # fall back to the always-feasible scan transfer and RECORD it
        # — explicit requests above still raise loudly, which is what
        # the no-silent-fallback guard asserts.
        scan = lookup(variant, "scan", cell, dtype)
        if scan is None:  # kernel-only custom variant: nothing to fall to
            raise
        _notify(Resolution(
            key=KernelKey(variant, "scan", cell, dtype),
            source="guard-fallback",
        ))
        return run(scan, "scan", None)


# ---------------------------------------------------------------------------
# Built-in implementations
# ---------------------------------------------------------------------------


def _scan_impl(module, attr: str) -> KernelFn:
    # Late-bound module attribute (not the function object): test spies
    # and user monkeypatches of e.g. matmul.cim_matmul_int must be seen
    # by dispatched executions too.
    def run(x_codes, w_codes, spec, *, key=None, planes=None, block=None):
        del block
        return getattr(module, attr)(
            x_codes, w_codes, spec, key=key, planes=planes
        )

    return run


def _ref_impl(module, attr: str) -> KernelFn:
    def run(x_codes, w_codes, spec, *, key=None, planes=None, block=None):
        del key, block  # noiseless vectorized formulation
        return getattr(module, attr)(x_codes, w_codes, spec, planes=planes)

    return run


def _pallas_blocks(
    spec: MacroSpec, block: tuple[int, int, int] | None
) -> tuple[int, int, int]:
    bm, bn, bk = block or (128, 128, 128)
    rows = spec.rows_active
    bk = max(rows, bk - bk % rows)  # kernel needs rows | bk
    return bm, bn, bk


def _slots_impl(attr: str) -> KernelFn:
    def run(x_codes, w_codes, spec, *, key=None, planes=None, slots=None,
            block=None):
        del w_codes, key, planes, block  # weight side IS the slot operand
        if slots is None:
            raise KernelInfeasible(
                "slots backend requires a plan's spread-slot operand "
                "grouped at the executing rows_active "
                "(engine.plan_weights(with_slots=True)); none provided"
            )
        return getattr(ref_lib, attr)(x_codes, slots, spec)

    return run


def _pallas_impl(kernel_name: str) -> KernelFn:
    def run(x_codes, w_codes, spec, *, key=None, planes=None, block=None):
        del key  # noiseless by design (production inference path)
        from repro.kernels import ops  # noqa: PLC0415 - optional pallas dep

        if planes is not None and planes.ndim == 3:
            # Packed plan planes [G, rows, N] uint8: bit b of each byte
            # is the weight's two's-complement bit b — exactly the
            # masked codes the kernel's in-tile unpack expects. The
            # flatten-slice recovers the [K, N] byte matrix at ANY
            # grouping (K-tail padding is all-zero bytes, dropped
            # here), so the resident int8 codes never re-load.
            k = x_codes.shape[1]
            w_codes = planes.reshape(-1, planes.shape[-1])[:k]
        bm, bn, bk = _pallas_blocks(spec, block)
        fn = getattr(ops, kernel_name)
        return fn(x_codes, w_codes, spec, bm=bm, bn=bn, bk=bk)

    return run


register_kernel(
    KernelKey("p8t", "scan"), _scan_impl(matmul_lib, "cim_matmul_int"),
    supports_noise=True, supports_planes=True,
)
register_kernel(
    KernelKey("p8t", "ref"), _ref_impl(ref_lib, "cim_matmul_ref"),
    supports_planes=True,
)
register_kernel(
    KernelKey("p8t", "slots"), _slots_impl("cim_matmul_slots"),
    supports_slots=True,
)
register_kernel(
    KernelKey("p8t", "pallas"), _pallas_impl("cim_matmul_kernel"),
    supports_planes=True, is_pallas=True,
)

# cell-adc: the ideal transfer equals the P-8T floor transfer, so scan
# and ref reuse those formulations; the Pallas kernel is the distinct
# per-row-reference SAR search (bit-identical codes).
register_kernel(
    KernelKey("cell-adc", "scan"), _scan_impl(matmul_lib, "cim_matmul_int"),
    supports_noise=True, supports_planes=True,
)
register_kernel(
    KernelKey("cell-adc", "ref"), _ref_impl(ref_lib, "cim_matmul_ref"),
    supports_planes=True,
)
register_kernel(
    KernelKey("cell-adc", "slots"), _slots_impl("cim_matmul_slots"),
    supports_slots=True,
)
register_kernel(
    KernelKey("cell-adc", "pallas"), _pallas_impl("cell_adc_matmul_kernel"),
    supports_planes=True, is_pallas=True,
)

register_kernel(
    KernelKey("adder-tree", "scan"),
    _scan_impl(variants_lib, "adder_tree_matmul_int"),
    supports_noise=True, supports_planes=True,
)
register_kernel(
    KernelKey("adder-tree", "ref"),
    _ref_impl(ref_lib, "adder_tree_matmul_ref"),
    supports_planes=True,
)
register_kernel(
    KernelKey("adder-tree", "slots"),
    _slots_impl("adder_tree_matmul_slots"),
    supports_slots=True,
)
register_kernel(
    KernelKey("adder-tree", "pallas"),
    _pallas_impl("adder_tree_matmul_kernel"),
    supports_planes=True, is_pallas=True,
)
