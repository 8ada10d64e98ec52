"""Fused decode-shape kernel paths (PR 9).

Covers the tentpole end to end:
  * the Pallas backend consuming a plan's *packed* bit planes directly
    (flatten-slice + in-tile unpack — no planes HBM round trip, no
    regroup on the hot path), bit-exact vs the integer oracles at
    non-tile decode shapes (m=1, odd K) for both adc modes;
  * the spread-slot "slots" backend: parity, explicit-request error
    when the plan operand is missing, the decode heuristic, and the
    rows-mismatch drop (slots cannot be regrouped);
  * the deep-K f32 guard: implicit picks fall back to scan loudly
    (record_resolutions), explicit requests still raise;
  * plan_weights(with_slots=) gating + engine.execute routing;
  * decode-shape tiling candidates and sweep versioning / staleness
    (swept_at vs sweep_version — counters, never wall clock).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, matmul, quant
from repro.core import variants as variants_lib
from repro.configs.base import CIMPolicy
from repro.core.params import PAPER_OP_16ROWS, CIMConfig
from repro.kernels import autotune, dispatch

RNG = np.random.default_rng(11)
VARIANTS = ("p8t", "adder-tree", "cell-adc")
# Non-tile decode shapes: m=1 and odd K hit every padding path (the
# Pallas K tail, the slot group tail, the [M, N] output crop).
SHAPES = ((1, 1001, 8), (3, 97, 24))
MODES = ("floor", "nearest")


def rand_codes(m, k, n, cfg):
    x = jnp.asarray(RNG.integers(0, cfg.act_levels, (m, k)), jnp.int32)
    lo = -(1 << (cfg.weight_bits - 1))
    hi = 1 << (cfg.weight_bits - 1)
    w = jnp.asarray(RNG.integers(lo, hi, (k, n)), jnp.int32)
    return x, w


def scan_oracle(variant, x, w, cfg):
    """The variant's integer-domain reference transfer (jnp scan)."""
    if variant == "adder-tree":
        return variants_lib.adder_tree_matmul_int(x, w, cfg)
    return matmul.cim_matmul_int(x, w, cfg)


@pytest.fixture(autouse=True)
def _no_ambient_tuning_cache():
    autotune.clear_active()
    yield
    autotune.clear_active()


class TestFusedPackedPlanes:
    """The Pallas kernels consume plan-packed planes without any
    unpack/regroup round trip — bit-exact vs the scan oracles."""

    @pytest.mark.parametrize("mode", MODES)
    @pytest.mark.parametrize("m,k,n", SHAPES)
    @pytest.mark.parametrize("variant", VARIANTS)
    def test_packed_planes_parity(self, variant, m, k, n, mode):
        cfg = PAPER_OP_16ROWS.replace(adc_mode=mode)
        x, w = rand_codes(m, k, n, cfg)
        planes = engine._grouped_planes(w, cfg, packed=True)
        assert planes.dtype == jnp.uint8
        want = np.asarray(scan_oracle(variant, x, w, cfg))
        got = dispatch.dispatch(
            x, w.astype(jnp.int8), cfg, variant=variant,
            backend="pallas", planes=planes,
        )
        np.testing.assert_array_equal(
            np.asarray(got), want, err_msg=f"{variant}/{mode}"
        )

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_int8_codes_parity(self, variant):
        """Narrow plan codes feed the kernel natively (no up-front
        widening in _tiled_call); parity vs the int32 path."""
        cfg = PAPER_OP_16ROWS
        x, w = rand_codes(1, 1001, 8, cfg)
        want = np.asarray(dispatch.dispatch(
            x, w, cfg, variant=variant, backend="pallas"
        ))
        got = dispatch.dispatch(
            x, w.astype(jnp.int8), cfg, variant=variant, backend="pallas"
        )
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=variant)

    def test_packed_planes_any_grouping(self):
        """The flatten-slice recovers the [K, N] byte matrix at ANY
        grouping — a calibration-grouped plan lowers without regroup."""
        cfg = PAPER_OP_16ROWS
        x, w = rand_codes(2, 1001, 8, cfg)
        planes8 = engine._grouped_planes(w, cfg, packed=True, rows=8)
        want = np.asarray(scan_oracle("p8t", x, w, cfg))
        got = dispatch.dispatch(
            x, w.astype(jnp.int8), cfg, backend="pallas", planes=planes8
        )
        np.testing.assert_array_equal(np.asarray(got), want)


class TestSlotsBackend:
    @pytest.mark.parametrize("mode", MODES)
    @pytest.mark.parametrize("m,k,n", SHAPES)
    @pytest.mark.parametrize("variant", VARIANTS)
    def test_slots_parity(self, variant, m, k, n, mode):
        cfg = PAPER_OP_16ROWS.replace(adc_mode=mode)
        x, w = rand_codes(m, k, n, cfg)
        slots = quant.spread_slots(
            w, cfg.rows_active, cfg.act_bits, cfg.weight_bits
        )
        want = np.asarray(scan_oracle(variant, x, w, cfg))
        got = dispatch.dispatch(
            x, w.astype(jnp.int8), cfg, variant=variant,
            backend="slots", slots=slots,
        )
        np.testing.assert_array_equal(
            np.asarray(got), want, err_msg=f"{variant}/{mode}"
        )

    def test_explicit_slots_without_operand_raises(self):
        cfg = PAPER_OP_16ROWS
        x, w = rand_codes(1, 32, 4, cfg)
        with pytest.raises(ValueError, match="spread-slot"):
            dispatch.dispatch(x, w, cfg, backend="slots")

    def test_heuristic_takes_slots_at_decode_shapes(self):
        cfg = PAPER_OP_16ROWS
        x, w = rand_codes(1, 64, 8, cfg)
        slots = quant.spread_slots(
            w, cfg.rows_active, cfg.act_bits, cfg.weight_bits
        )
        with dispatch.record_resolutions() as log:
            y = dispatch.dispatch(x, w, cfg, slots=slots)
        assert log[0].source == "heuristic"
        assert log[0].key.backend == "slots"
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(scan_oracle("p8t", x, w, cfg))
        )
        # past the decode regime the heuristic leaves slots alone
        x2, w2 = rand_codes(64, 64, 8, cfg)
        slots2 = quant.spread_slots(
            w2, cfg.rows_active, cfg.act_bits, cfg.weight_bits
        )
        with dispatch.record_resolutions() as log:
            dispatch.dispatch(x2, w2, cfg, slots=slots2)
        assert log[0].key.backend != "slots"

    def test_rows_mismatch_drops_slots(self):
        """Slots grouped for a different rows_active are unusable (the
        fields bake the grouping in) — dropped, never mis-decoded."""
        cfg = PAPER_OP_16ROWS
        x, w = rand_codes(1, 64, 8, cfg)
        slots8 = quant.spread_slots(w, 8, cfg.act_bits, cfg.weight_bits)
        with dispatch.record_resolutions() as log:
            y = dispatch.dispatch(x, w, cfg, slots=slots8)
        assert log[0].key.backend != "slots"
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(scan_oracle("p8t", x, w, cfg))
        )
        with pytest.raises(ValueError, match="spread-slot"):
            dispatch.dispatch(x, w, cfg, backend="slots", slots=slots8)

    def test_noise_still_routes_to_scan_past_slots(self):
        import jax

        cfg = PAPER_OP_16ROWS.replace(noisy=True)
        x, w = rand_codes(1, 64, 8, cfg)
        slots = quant.spread_slots(
            w, cfg.rows_active, cfg.act_bits, cfg.weight_bits
        )
        with dispatch.record_resolutions() as log:
            dispatch.dispatch(x, w, cfg, key=jax.random.PRNGKey(0),
                              slots=slots)
        assert log[0].source == "noise"
        assert log[0].key.backend == "scan"


class TestDeepKGuard:
    """K too deep for exact f32 accumulation: the Pallas kernel raises
    at trace time; implicit picks fall back to scan AND record it."""

    CFG = CIMConfig(rows_active=4, weight_bits=4, cutoff=0.5, adc_bits=4)
    M, K, N = 1, 1 << 18, 2  # past the guard at rows_active=4

    def test_explicit_pallas_raises(self):
        x, w = rand_codes(self.M, self.K, self.N, self.CFG)
        with pytest.raises(ValueError, match="too deep"):
            dispatch.dispatch(x, w, self.CFG, backend="pallas")

    def test_implicit_tuned_pin_falls_back_to_scan(self):
        x, w = rand_codes(self.M, self.K, self.N, self.CFG)
        cache = autotune.TuningCache(arch="test")
        cache.put("p8t", dispatch.shape_cell(self.M, self.K, self.N),
                  autotune.Winner("pallas", None, 1.0))
        autotune.set_active(cache)
        with dispatch.record_resolutions() as log:
            y = dispatch.dispatch(x, w, self.CFG)
        assert [r.source for r in log] == ["tuned", "guard-fallback"]
        assert log[-1].key.backend == "scan"
        np.testing.assert_array_equal(
            np.asarray(y),
            np.asarray(matmul.cim_matmul_int(x, w, self.CFG)),
        )


class TestPlanSlots:
    """plan_weights precomputes the slot operand for plannable layers
    and engine.execute serves decode steps through it."""

    def test_plan_carries_slots_and_execute_routes(self):
        cfg = PAPER_OP_16ROWS
        policy = CIMPolicy(mode="cim", cim=cfg, ste=False)
        w = jnp.asarray(RNG.normal(size=(96, 8)) * 0.1, jnp.float32)
        x = jnp.asarray(RNG.normal(size=(1, 96)).clip(-3, 3), jnp.float32)
        plan = engine.plan_weights(w, cfg, policy, with_planes=True)
        assert plan.slots is not None
        assert plan.slots.shape[-2] == cfg.rows_active
        with dispatch.record_resolutions() as log:
            y = engine.execute(x, plan, policy)
        assert log and log[0].key.backend == "slots"
        assert np.all(np.isfinite(np.asarray(y)))
        # pinning scan for the cell is bit-identical (fused = unfused)
        cache = autotune.TuningCache(arch="test")
        cache.put("p8t", dispatch.shape_cell(1, 96, 8),
                  autotune.Winner("scan", None, 1.0))
        autotune.set_active(cache)
        np.testing.assert_array_equal(
            np.asarray(engine.execute(x, plan, policy)), np.asarray(y)
        )

    def test_with_slots_gating(self):
        import jax

        cfg = PAPER_OP_16ROWS
        policy = CIMPolicy(mode="cim", cim=cfg, ste=False)
        big = jax.ShapeDtypeStruct((4096, 2048), jnp.float32)
        small = jax.ShapeDtypeStruct((96, 8), jnp.float32)
        tree = engine.plan_params(
            {"big": {"w": big}, "small": {"w": small}}, cfg, policy
        )
        assert tree["big"]["w"].slots is None  # > SLOTS_MAX_ELEMS weights
        assert tree["small"]["w"].slots is not None
        assert tree["small"]["w"].slots.shape == engine._slots_shape(
            96, 8, cfg
        )

    def test_with_slots_explicit_override(self):
        cfg = PAPER_OP_16ROWS
        policy = CIMPolicy(mode="cim", cim=cfg, ste=False)
        w = jnp.asarray(RNG.normal(size=(64, 8)) * 0.1, jnp.float32)
        plan = engine.plan_weights(
            w, cfg, policy, with_planes=True, with_slots=False
        )
        assert plan.slots is None


class TestDecodeBlocks:
    def test_rows_aligned_and_capped(self):
        for rows in (4, 8, 12, 16):
            for m in (1, 3, 16, None):
                blocks = autotune.decode_blocks(rows, m)
                assert blocks, (rows, m)
                for bm, bn, bk in blocks:
                    assert bm in autotune.DECODE_BMS
                    assert bk % rows == 0, (rows, bk)
                    # TPU (8, 128) block rule: bm=1 only when m == 1.
                    if m == 1:
                        assert bm == 1
                        continue
                    assert bm % 8 == 0, (m, bm)
                    if m is not None:
                        cap = 8
                        while cap < m and cap < max(autotune.DECODE_BMS):
                            cap *= 2
                        assert bm <= cap

    def test_m1_sweeps_only_bm1(self):
        assert {b[0] for b in autotune.decode_blocks(16, 1)} == {1}

    def test_candidates_extend_with_decode_blocks(self):
        cands = autotune.default_candidates(
            "p8t", include_pallas=True, rows=16, m=1
        )
        pallas_blocks = [b for be, b in cands if be == "pallas"]
        assert len(set(pallas_blocks)) == len(pallas_blocks)  # deduped
        assert any(b[0] == 1 for b in pallas_blocks)  # decode bm present
        assert ("slots", None) in cands

    def test_sweep_shape_times_slots(self):
        """The sweep builds the planned operands, so "slots" is a live
        candidate (regression: a traced-float readback once made it
        lose every sweep by raising under jit)."""
        order = {"scan": 3.0, "ref": 2.0, "slots": 1.0, "pallas": 4.0}
        win = autotune.sweep_shape(
            "p8t", PAPER_OP_16ROWS, 1, 64, 8,
            measure=lambda cand, run: (run(), order[cand[0]])[1],
        )
        assert win.backend == "slots"


class TestSweepVersioning:
    def test_winner_round_trip_with_swept_at(self):
        w = autotune.Winner("ref", None, 12.5, swept_at=3)
        assert autotune.Winner.from_json(w.to_json()) == w
        # pre-versioning entries read back as swept_at=0
        legacy = {"backend": "scan", "block": None, "us": 1.0}
        assert autotune.Winner.from_json(legacy).swept_at == 0

    def test_cache_from_records_stamps_and_inherits(self):
        prev = autotune.TuningCache(arch="cpu", sweep_version=2)
        prev.put("p8t", (8, 512, 512),
                 autotune.Winner("ref", None, 1.0, swept_at=2))
        prev.put("p8t", (1, 64, 64),
                 autotune.Winner("scan", None, 1.0, swept_at=1))
        cache = autotune.cache_from_records(
            "cpu",
            [{"variant": "p8t", "cell": [1, 64, 64],
              "backend": "slots", "block": None, "us": 0.5}],
            prev=prev,
        )
        assert cache.sweep_version == 3
        assert cache.entries["p8t/m1_k64_n64"].swept_at == 3
        assert cache.entries["p8t/m1_k64_n64"].backend == "slots"
        # the inherited cell keeps its old stamp and reads as stale
        assert cache.entries["p8t/m8_k512_n512"].swept_at == 2
        assert autotune.stale_entries(cache) == ("p8t/m8_k512_n512",)

    def test_autotune_merge_bumps_version(self, tmp_path):
        meas = lambda cand, run: (run(), {"scan": 1.0, "ref": 2.0,
                                          "slots": 3.0}[cand[0]])[1]
        path = tmp_path / "arch.json"
        c1 = autotune.autotune(
            [(4, 64, 8)], PAPER_OP_16ROWS, variants=("p8t",),
            measure=meas, path=path, activate=False,
        )
        assert c1.sweep_version == 1
        c2 = autotune.autotune(
            [(8, 128, 8)], PAPER_OP_16ROWS, variants=("p8t",),
            measure=meas, path=path, activate=False,
        )
        assert c2.sweep_version == 2
        assert autotune.stale_entries(c2) == ("p8t/m4_k64_n8",)
        # a full re-sweep clears the staleness report
        c3 = autotune.autotune(
            [(4, 64, 8), (8, 128, 8)], PAPER_OP_16ROWS, variants=("p8t",),
            measure=meas, path=path, activate=False,
        )
        assert autotune.stale_entries(c3) == ()

    def test_committed_cpu_cache_loads_and_is_fresh(self):
        """The shipped results/autotune/cpu.json parses, covers the
        decode (m=1) and batch (m=512) regimes for every variant, and
        carries no stale entries."""
        cache = autotune.TuningCache.load(arch="cpu")
        assert cache is not None
        cells = {}
        for key in cache.entries:
            variant, cell = key.split("/")
            cells.setdefault(variant, set()).add(cell)
        for variant in VARIANTS:
            assert len(cells.get(variant, ())) >= 8, variant
            assert any(c.startswith("m1_") for c in cells[variant])
            assert any(c.startswith("m512_") for c in cells[variant])
        assert autotune.stale_entries(cache) == ()
