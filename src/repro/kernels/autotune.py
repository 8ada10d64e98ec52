"""Per-(arch, variant, shape-cell) kernel autotuning with a JSON cache.

The dispatch heuristics pick a safe default; this module replaces them
with *measured* winners: :func:`sweep_shape` times every registered
backend (and, for Pallas, every candidate block size) of one variant
at one representative shape, :func:`autotune` runs the sweep over a
shape/variant grid, and the winners persist to a JSON cache under
``results/autotune/<arch>.json`` that ``dispatch`` consults before its
heuristics — so a tuned deployment keeps its per-shape choices across
processes with a deterministic re-load path (no re-timing at serve
time).

Cache file format (version 1)::

    {
      "version": 1,
      "arch": "cpu",
      "sweep_version": 3,
      "entries": {
        "p8t/m8_k1024_n1024":  {"backend": "ref", "block": null,
                                "us": 812.4, "swept_at": 3},
        "p8t/m128_k1024_n1024": {"backend": "pallas",
                                 "block": [128, 128, 128],
                                 "us": 95.1, "swept_at": 2}
      }
    }

Keys are ``<variant>/m<cell>_k<cell>_n<cell>`` over the power-of-two
cells of :func:`dispatch.shape_cell`; ``block`` is the pinned Pallas
tiling (null for jnp backends). Entries are written sorted, so the
same sweep produces byte-identical files (round-trip determinism is
property-tested).

``sweep_version`` is a monotone counter bumped by every merging
:func:`autotune` run, and each entry records the ``swept_at`` version
that last measured it — NOT a wall-clock stamp (artifact determinism,
CIM201), but enough for :func:`stale_entries` to flag cells a partial
re-sweep left behind (surfaced by ``repro.sweep``'s ``--analyze``
autotune renderer).

Timing is injectable (``measure=``) so tests pin winners with a
deterministic proxy; the default measures best-of-``reps`` wall time
of a jitted call. Candidates that raise ``KernelInfeasible`` at the
shape (e.g. a depth-guarded Pallas kernel) are skipped and logged,
never winners; any other error (a kernel the compiler refuses)
propagates.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import pathlib
import time
from typing import Any, Callable, Iterable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.params import CIMConfig
from repro.core.pipeline import MacroSpec, as_spec
from repro.kernels import dispatch

CACHE_VERSION = 1

logger = logging.getLogger(__name__)

# Pallas tiling candidates swept per shape (bk is clamped to a multiple
# of rows_active by the dispatch adapter).
PALLAS_BLOCKS: tuple[tuple[int, int, int], ...] = (
    (128, 128, 128),
    (64, 128, 128),
    (32, 64, 128),
)

# Small-bm candidates for the decode shapes (see decode_blocks).
DECODE_BMS: tuple[int, ...] = (1, 8, 16)


def decode_blocks(
    rows: int, m: int | None = None, *, bn: int = 128
) -> tuple[tuple[int, int, int], ...]:
    """Decode-shape Pallas tiling candidates.

    A 128-row M tile pads an m=1 decode step to 128 rows and burns
    128x the FLOPs (the default block, ``dispatch._pallas_blocks``,
    already follows M down to 8 rows); these candidates pair small bm
    values (``DECODE_BMS``) with bk values aligned to the calibration's
    ``rows_active`` group (the kernel requires rows | bk, and a
    rows-aligned bk avoids the dispatch adapter's round-down losing
    contraction depth for non-power-of-two rows).

    A TPU block's second-minor dim must be a multiple of 8 or the whole
    (padded) array dim, so bm=1 is legal only for an m=1 call: an m=1
    sweep times bm=1 alone, and any other (or unknown) m times the bm
    values that are multiples of 8, up to the next power of two of
    max(m, 8).
    """
    if m == 1:
        bms = (1,)
    else:
        bms = tuple(
            bm for bm in DECODE_BMS
            if bm % 8 == 0 and (m is None or bm < 2 * max(m, 8))
        )
    bks = sorted({max(rows, 128 - 128 % rows), 8 * rows})
    return tuple((bm, bn, bk) for bm in bms for bk in bks)

Candidate = tuple[str, tuple[int, int, int] | None]
# measure(candidate, run) -> seconds for one call; `run` executes the
# (already warmed/compiled) candidate once, blocking on the result.
MeasureFn = Callable[[Candidate, Callable[[], Any]], float]


def default_cache_dir() -> pathlib.Path:
    """results/autotune under the repo root (env-overridable)."""
    env = os.environ.get("REPRO_AUTOTUNE_DIR")
    if env:
        return pathlib.Path(env)
    return (
        pathlib.Path(__file__).resolve().parents[3] / "results" / "autotune"
    )


def cache_path(arch: str) -> pathlib.Path:
    return default_cache_dir() / f"{arch}.json"


@dataclasses.dataclass(frozen=True)
class Winner:
    """The pinned choice for one (variant, shape cell).

    ``swept_at`` is the cache's ``sweep_version`` when this entry was
    last measured (0 = predates versioned sweeps); it is bookkeeping
    for staleness reporting and does not affect dispatch.
    """

    backend: str
    block: tuple[int, int, int] | None
    us: float
    swept_at: int = 0

    def to_json(self) -> dict:
        return {
            "backend": self.backend,
            "block": list(self.block) if self.block else None,
            "us": self.us,
            "swept_at": self.swept_at,
        }

    @classmethod
    def from_json(cls, d: Mapping) -> "Winner":
        block = d.get("block")
        return cls(
            backend=d["backend"],
            block=tuple(block) if block else None,
            us=float(d.get("us", 0.0)),
            swept_at=int(d.get("swept_at", 0)),
        )


def cell_id(variant: str, cell: tuple[int, int, int]) -> str:
    return f"{variant}/m{cell[0]}_k{cell[1]}_n{cell[2]}"


@dataclasses.dataclass
class TuningCache:
    """The per-arch winner table, JSON round-trippable.

    ``sweep_version`` counts merging :func:`autotune` runs; entries
    whose ``swept_at`` lags it were inherited from an earlier sweep
    (see :func:`stale_entries`).
    """

    arch: str
    entries: dict[str, Winner] = dataclasses.field(default_factory=dict)
    sweep_version: int = 0

    def lookup(
        self, variant: str, cell: tuple[int, int, int]
    ) -> Winner | None:
        return self.entries.get(cell_id(variant, cell))

    def put(
        self, variant: str, cell: tuple[int, int, int], winner: Winner
    ) -> None:
        self.entries[cell_id(variant, cell)] = winner

    def to_json(self) -> dict:
        return {
            "version": CACHE_VERSION,
            "arch": self.arch,
            "sweep_version": self.sweep_version,
            "entries": {
                k: self.entries[k].to_json() for k in sorted(self.entries)
            },
        }

    @classmethod
    def from_json(cls, d: Mapping) -> "TuningCache":
        if d.get("version") != CACHE_VERSION:
            raise ValueError(
                f"tuning cache version {d.get('version')} != "
                f"{CACHE_VERSION}; re-run kernels.autotune.autotune"
            )
        return cls(
            arch=d.get("arch", "unknown"),
            entries={
                k: Winner.from_json(v) for k, v in d["entries"].items()
            },
            sweep_version=int(d.get("sweep_version", 0)),
        )

    def save(self, path: pathlib.Path | str | None = None) -> pathlib.Path:
        path = pathlib.Path(path) if path else cache_path(self.arch)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.to_json(), indent=1, sort_keys=True))
        return path

    @classmethod
    def load(
        cls,
        arch: str | None = None,
        path: pathlib.Path | str | None = None,
    ) -> "TuningCache | None":
        """Deterministic re-load: None when no cache was ever written."""
        path = pathlib.Path(path) if path else cache_path(
            arch or jax.default_backend()
        )
        if not path.exists():
            return None
        return cls.from_json(json.loads(path.read_text()))


# ---------------------------------------------------------------------------
# The active cache dispatch consults
# ---------------------------------------------------------------------------

_active: TuningCache | None = None
_loaded = False


def active_cache() -> TuningCache | None:
    """The cache dispatch consults; lazily loaded from results/ once.

    The file is an optional *hint*: a stale-version or corrupt cache
    must degrade to the dispatch heuristics (with a one-time warning),
    never brick serving — and a cache that was simply never written
    for this arch (only ``cpu.json`` ships today) degrades the same
    way, with a one-time log line naming the missing file. Explicit
    ``TuningCache.load`` calls keep their strict errors.
    """
    global _active, _loaded
    if not _loaded:
        arch = jax.default_backend()
        try:
            _active = TuningCache.load()
            if _active is None:
                logger.info(
                    "no tuning cache for arch '%s' (%s missing): "
                    "kernel dispatch falls back to the deterministic "
                    "heuristics; run kernels.autotune.autotune (or a "
                    "configs/sweeps/autotune_*.json sweep) to pin "
                    "measured winners",
                    arch, cache_path(arch),
                )
        except Exception as e:  # noqa: BLE001 - degrade, don't brick
            import warnings

            warnings.warn(
                f"ignoring unreadable tuning cache "
                f"({cache_path(arch)}): {e}; "
                "re-run kernels.autotune.autotune to regenerate",
                stacklevel=2,
            )
            _active = None
        _loaded = True
    return _active


def set_active(cache: TuningCache | None) -> None:
    global _active, _loaded
    _active, _loaded = cache, True


def clear_active() -> None:
    """Disable tuned dispatch for this process (heuristics only)."""
    set_active(None)


def reload_active() -> TuningCache | None:
    """Force a re-read from the default cache path."""
    global _loaded
    _loaded = False
    return active_cache()


def lookup(variant: str, cell: tuple[int, int, int]) -> Winner | None:
    cache = active_cache()
    return None if cache is None else cache.lookup(variant, cell)


def stale_entries(cache: TuningCache) -> tuple[str, ...]:
    """Entry ids whose winner predates the cache's latest sweep.

    A partial re-sweep (``autotune(merge=True)`` over a subset of
    cells) bumps ``sweep_version`` and stamps only the swept cells;
    everything it inherited keeps its old ``swept_at`` and shows up
    here — including ``swept_at=0`` entries from pre-versioning
    caches, which is exactly the single-entry-cache staleness this
    reporting exists to surface.
    """
    return tuple(sorted(
        k for k, w in cache.entries.items()
        if w.swept_at < cache.sweep_version
    ))


# ---------------------------------------------------------------------------
# Sweeping
# ---------------------------------------------------------------------------


def cache_from_records(
    arch: str, records: Iterable[Mapping],
    prev: TuningCache | None = None,
) -> TuningCache:
    """A TuningCache from measured-winner records (the sweep harness).

    Each record carries ``variant``, ``cell`` ([m, k, n] tuning cell),
    ``backend``, ``block`` and ``us``. Later records win a shared
    cell, matching :func:`autotune`'s last-sweep-wins merge. ``prev``
    (e.g. the committed per-arch cache) seeds inherited entries at
    their old ``swept_at``; the fresh records stamp the bumped
    ``sweep_version``, so :func:`stale_entries` of the result is the
    not-re-swept remainder.
    """
    cache = TuningCache(arch=arch)
    if prev is not None:
        cache.entries.update(prev.entries)
        cache.sweep_version = prev.sweep_version
    cache.sweep_version += 1
    for r in records:
        cache.put(
            r["variant"], tuple(int(d) for d in r["cell"]),
            Winner(
                backend=r["backend"],
                block=tuple(r["block"]) if r.get("block") else None,
                us=float(r.get("us", 0.0)),
                swept_at=cache.sweep_version,
            ),
        )
    return cache


def default_candidates(
    variant: str,
    *,
    blocks: Sequence[tuple[int, int, int]] = PALLAS_BLOCKS,
    include_pallas: bool | None = None,
    rows: int | None = None,
    m: int | None = None,
) -> tuple[Candidate, ...]:
    """Candidate (backend, block) pairs for one variant, stable order.

    ``include_pallas`` defaults to native-lowering only (TPU): in
    interpret mode the kernel is a correctness vehicle, and timing it
    would never pin it anyway — skipping keeps sweeps fast on CPU.
    Pass True to sweep it regardless. With ``rows`` (the operating
    point's ``rows_active``) the Pallas block list extends with the
    :func:`decode_blocks` small-bm / rows-aligned-bk candidates for
    the sweep's ``m``.
    """
    if include_pallas is None:
        include_pallas = jax.default_backend() == "tpu"
    if rows is not None:
        seen = set(blocks)
        blocks = tuple(blocks) + tuple(
            b for b in decode_blocks(rows, m) if b not in seen
        )
    cands: list[Candidate] = []
    for backend in dispatch.backends_for(variant):
        if dispatch.lookup(variant, backend) is None:
            continue
        if backend == "pallas":
            if include_pallas:
                cands.extend(("pallas", b) for b in blocks)
        else:
            cands.append((backend, None))
    return tuple(cands)


def _wall_measure(reps: int) -> MeasureFn:
    def measure(candidate: Candidate, run: Callable[[], Any]) -> float:
        del candidate
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            run()
            best = min(best, time.perf_counter() - t0)
        return best

    return measure


def sweep_shape(
    variant: str,
    spec: CIMConfig | MacroSpec | None,
    m: int,
    k: int,
    n: int,
    *,
    candidates: Sequence[Candidate] | None = None,
    measure: MeasureFn | None = None,
    reps: int = 3,
    seed: int = 0,
) -> Winner:
    """Time every candidate at one shape; return the pinned winner.

    Deterministic given a deterministic ``measure``: candidates are
    evaluated in their stable enumeration order and ties keep the
    earlier candidate.

    Every candidate is timed against the operands a *served* plan
    provides — narrow integer codes plus the planned packed planes and
    spread-slot tensors — so winners reflect the traffic the serving
    path actually pays (and plan-dependent backends like "slots" are
    sweepable at all; infeasible ones skip, never win).
    """
    spec = as_spec(spec) if spec is not None else MacroSpec()
    spec = spec.replace(noisy=False)
    if candidates is None:
        candidates = default_candidates(
            variant, rows=spec.rows_active, m=m
        )
    if measure is None:
        measure = _wall_measure(reps)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, spec.act_levels, (m, k)), jnp.int32)
    lo = -(1 << (spec.weight_bits - 1))
    hi = 1 << (spec.weight_bits - 1)
    cdtype = jnp.int8 if spec.weight_bits <= 8 else jnp.int32
    w = jnp.asarray(rng.integers(lo, hi, (k, n)), cdtype)

    from repro.core import engine  # noqa: PLC0415 - lazy, no cycle
    from repro.core import quant  # noqa: PLC0415

    planes = None
    if spec.weight_bits <= 8:
        planes = engine._grouped_planes(
            w.astype(jnp.int32), spec, packed=True
        )
    try:
        slots = quant.spread_slots(
            w.astype(jnp.int32), spec.rows_active, spec.act_bits,
            spec.weight_bits,
        )
    except ValueError:  # infeasible operating point for slot packing
        slots = None

    best: Winner | None = None
    for backend, block in candidates:
        fn = jax.jit(
            lambda xx, ww, pp, ss, _b=backend, _blk=block:
            dispatch.dispatch(
                xx, ww, spec, variant=variant, backend=_b, block=_blk,
                planes=pp, slots=ss,
            )
        )
        try:
            jax.block_until_ready(fn(x, w, planes, slots))
        except dispatch.KernelInfeasible as e:
            logger.info(
                "autotune %s (%d, %d, %d): skipping %s block=%s: %s",
                variant, m, k, n, backend, block, e,
            )
            continue
        secs = float(measure(
            (backend, block),
            lambda: jax.block_until_ready(fn(x, w, planes, slots)),
        ))
        if best is None or secs * 1e6 < best.us:
            best = Winner(backend=backend, block=block, us=secs * 1e6)
    if best is None:
        raise RuntimeError(
            f"no feasible kernel candidate for variant='{variant}' at "
            f"shape ({m}, {k}, {n})"
        )
    return best


def autotune(
    shapes: Iterable[tuple[int, int, int]],
    spec: CIMConfig | MacroSpec | None = None,
    *,
    variants: Sequence[str] = ("p8t", "adder-tree", "cell-adc"),
    arch: str | None = None,
    save: bool = True,
    path: pathlib.Path | str | None = None,
    activate: bool = True,
    merge: bool = True,
    **sweep_kw,
) -> TuningCache:
    """Sweep a (variants x shapes) grid and persist/activate the winners.

    One entry per (variant, shape cell); when several concrete shapes
    fall in one cell the last sweep wins (pass one representative per
    cell). With ``save`` the cache lands at ``results/autotune/`` (or
    ``path``); with ``activate`` it becomes the cache dispatch
    consults in this process. ``merge`` (default) seeds the result
    with the previously persisted entries for this arch, so a partial
    re-sweep updates only the swept cells instead of discarding every
    other pinned winner; pass ``merge=False`` to start clean. Either
    way ``sweep_version`` bumps and the freshly swept cells are
    stamped with it — inherited cells keep their old ``swept_at`` and
    show up in :func:`stale_entries`.
    """
    arch = arch or jax.default_backend()
    shapes = tuple(shapes)  # generators must survive the variant loop
    cache = TuningCache(arch=arch)
    if merge:
        prev = TuningCache.load(arch=arch, path=path)
        if prev is not None:
            cache.entries.update(prev.entries)
            cache.sweep_version = prev.sweep_version
    cache.sweep_version += 1
    for variant in variants:
        for (m, k, n) in shapes:
            cell = dispatch.shape_cell(m, k, n)
            win = sweep_shape(variant, spec, m, k, n, **sweep_kw)
            cache.put(
                variant, cell,
                dataclasses.replace(win, swept_at=cache.sweep_version),
            )
    if save:
        cache.save(path)
    if activate:
        set_active(cache)
    return cache
