"""Unified variant-aware kernel dispatch (kernels.dispatch/autotune).

Covers the PR-4 tentpole end to end:
  * Pallas (interpret-mode) parity vs the integer oracles for every
    registered KernelKey of every variant;
  * routing: explicit requests are honored (never silently scanned),
    noise routes to the scan transfer, the tuning cache is consulted
    before heuristics, registering a MacroVariant auto-wires its scan;
  * the autotune sweep/cache: deterministic winners, JSON round trip,
    results/-anchored reload path;
  * plan_params(calibration=...) groups planes at each layer's
    calibrated rows_active so the analog backend never regroups.
"""

import dataclasses
import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import CIMPolicy
from repro.core import calibrate as cal
from repro.core import engine, matmul, quant
from repro.core import variants as variants_lib
from repro.core.params import PAPER_OP_16ROWS, CIMConfig
from repro.core.pipeline import default_pipeline
from repro.kernels import autotune, dispatch

RNG = np.random.default_rng(7)
VARIANTS = ("p8t", "adder-tree", "cell-adc")


def rand_codes(m, k, n, cfg):
    x = jnp.asarray(RNG.integers(0, cfg.act_levels, (m, k)), jnp.int32)
    lo = -(1 << (cfg.weight_bits - 1))
    hi = 1 << (cfg.weight_bits - 1)
    w = jnp.asarray(RNG.integers(lo, hi, (k, n)), jnp.int32)
    return x, w


def slot_operand(w, cfg):
    """The plan's spread-slot operand (the "slots" backend requires it)."""
    return quant.spread_slots(
        w, cfg.rows_active, cfg.act_bits, cfg.weight_bits
    )


def scan_oracle(variant, x, w, cfg, *, key=None, planes=None):
    """The variant's integer-domain reference transfer (jnp scan)."""
    if variant == "adder-tree":
        return variants_lib.adder_tree_matmul_int(
            x, w, cfg, key=key, planes=planes
        )
    return matmul.cim_matmul_int(x, w, cfg, key=key, planes=planes)


@pytest.fixture(autouse=True)
def _no_ambient_tuning_cache():
    """Tests pin routing explicitly; don't let results/ leak in."""
    autotune.clear_active()
    yield
    autotune.clear_active()


class TestKernelKeyParity:
    """Every registered backend of every variant is bit-exact vs the
    variant's integer oracle (Pallas in interpret mode on CPU)."""

    @pytest.mark.parametrize("variant", VARIANTS)
    @pytest.mark.parametrize("m,k,n", [(4, 16, 8), (7, 100, 5),
                                       (16, 128, 24)])
    def test_backends_match_oracle(self, variant, m, k, n):
        cfg = PAPER_OP_16ROWS
        x, w = rand_codes(m, k, n, cfg)
        want = np.asarray(scan_oracle(variant, x, w, cfg))
        slots = slot_operand(w, cfg)
        for backend in dispatch.backends_for(variant):
            got = dispatch.dispatch(
                x, w, cfg, variant=variant, backend=backend, slots=slots
            )
            np.testing.assert_array_equal(
                np.asarray(got), want, err_msg=f"{variant}/{backend}"
            )

    @pytest.mark.parametrize("variant", VARIANTS)
    @pytest.mark.parametrize("rows,bits", [(8, 8), (16, 4)])
    def test_operating_points(self, variant, rows, bits):
        cfg = CIMConfig(rows_active=rows, weight_bits=bits,
                        cutoff=0.5, adc_bits=4)
        x, w = rand_codes(8, 48, 6, cfg)
        want = np.asarray(scan_oracle(variant, x, w, cfg))
        slots = slot_operand(w, cfg)
        for backend in dispatch.backends_for(variant):
            got = dispatch.dispatch(
                x, w, cfg, variant=variant, backend=backend, slots=slots
            )
            np.testing.assert_array_equal(
                np.asarray(got), want,
                err_msg=f"{variant}/{backend} rows={rows} bits={bits}",
            )

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_every_variant_has_pallas(self, variant):
        assert dispatch.has_pallas(variant)

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_nearest_mode_parity(self, variant):
        """adc_mode='nearest' must round identically on every backend
        (regression: the ref/pallas formulations once hardcoded floor)."""
        cfg = PAPER_OP_16ROWS.replace(adc_mode="nearest")
        x, w = rand_codes(6, 80, 7, cfg)
        want = np.asarray(scan_oracle(variant, x, w, cfg))
        # nearest genuinely differs from floor here, so parity is
        # meaningful (guard against a vacuous test)
        floor = np.asarray(scan_oracle(variant, x, w, PAPER_OP_16ROWS))
        assert not np.array_equal(want, floor)
        slots = slot_operand(w, cfg)
        for backend in dispatch.backends_for(variant):
            got = dispatch.dispatch(
                x, w, cfg, variant=variant, backend=backend, slots=slots
            )
            np.testing.assert_array_equal(
                np.asarray(got), want, err_msg=f"{variant}/{backend}"
            )

    @pytest.mark.parametrize("pack", [False, True],
                             ids=["unpacked", "packed"])
    @pytest.mark.parametrize("variant", VARIANTS)
    def test_planes_paths_match(self, variant, pack):
        """scan/ref consume plan-grouped planes; parity either way."""
        cfg = PAPER_OP_16ROWS
        x, w = rand_codes(5, 48, 8, cfg)
        planes = engine._grouped_planes(w, cfg, packed=pack)
        want = np.asarray(scan_oracle(variant, x, w, cfg))
        for backend in ("scan", "ref"):
            got = dispatch.dispatch(
                x, w, cfg, variant=variant, backend=backend, planes=planes
            )
            np.testing.assert_array_equal(
                np.asarray(got), want, err_msg=f"{variant}/{backend}"
            )


class TestRouting:
    def test_explicit_pallas_never_scans(self):
        cfg = PAPER_OP_16ROWS
        x, w = rand_codes(4, 32, 4, cfg)
        for variant in VARIANTS:
            with dispatch.record_resolutions() as log:
                dispatch.dispatch(
                    x, w, cfg, variant=variant, backend="pallas"
                )
            assert [r.key.backend for r in log] == ["pallas"], variant
            assert log[0].source == "explicit"

    def test_noise_routes_to_scan_and_matches_behavioral(self):
        cfg = PAPER_OP_16ROWS.replace(noisy=True)
        x, w = rand_codes(4, 64, 4, cfg)
        key = jax.random.PRNGKey(3)
        with dispatch.record_resolutions() as log:
            y = dispatch.dispatch(x, w, cfg, key=key)
        assert log[0].source == "noise"
        assert log[0].key.backend == "scan"
        np.testing.assert_array_equal(
            np.asarray(y),
            np.asarray(matmul.cim_matmul_int(x, w, cfg, key=key)),
        )

    def test_tuned_cache_consulted_before_heuristics(self):
        cfg = PAPER_OP_16ROWS
        x, w = rand_codes(4, 32, 4, cfg)
        cache = autotune.TuningCache(arch="test")
        cache.put("p8t", dispatch.shape_cell(4, 32, 4),
                  autotune.Winner("ref", None, 1.0))
        autotune.set_active(cache)
        with dispatch.record_resolutions() as log:
            dispatch.dispatch(x, w, cfg)
        assert log[0].source == "tuned"
        assert log[0].key.backend == "ref"
        # other cells still fall through to the heuristic
        x2, w2 = rand_codes(64, 256, 64, cfg)
        with dispatch.record_resolutions() as log:
            dispatch.dispatch(x2, w2, cfg)
        assert log[0].source == "heuristic"

    def test_unknown_backend_raises(self):
        cfg = PAPER_OP_16ROWS
        x, w = rand_codes(2, 16, 2, cfg)
        with pytest.raises(KeyError, match="no kernel registered"):
            dispatch.dispatch(x, w, cfg, backend="nope")

    def test_heuristic_keeps_planes_on_scan(self):
        """Implicit routing must not discard plan planes for a
        planes-blind kernel — the weight-stationary plan wins."""
        cfg = PAPER_OP_16ROWS
        x, w = rand_codes(4, 48, 4, cfg)
        planes = engine._grouped_planes(w, cfg)
        with dispatch.record_resolutions() as log:
            dispatch.dispatch(x, w, cfg, planes=planes)
        assert log[0].key.backend == "scan"

    def test_infeasible_tuned_pin_falls_back_to_scan_loudly(self):
        """A stale/infeasible tuned winner must not kill implicit
        dispatch: it falls back to scan AND records the fallback;
        an explicit request still raises."""
        def boom(xc, wc, spec, *, key=None, planes=None, block=None):
            raise dispatch.KernelInfeasible("infeasible at this shape")

        kk = dispatch.register_kernel(
            dispatch.KernelKey("p8t", "boom"), boom
        )
        cfg = PAPER_OP_16ROWS
        x, w = rand_codes(3, 32, 4, cfg)
        cache = autotune.TuningCache(arch="test")
        cache.put("p8t", dispatch.shape_cell(3, 32, 4),
                  autotune.Winner("boom", None, 1.0))
        autotune.set_active(cache)
        try:
            with dispatch.record_resolutions() as log:
                y = dispatch.dispatch(x, w, cfg)
            assert [r.source for r in log] == ["tuned", "guard-fallback"]
            assert log[-1].key.backend == "scan"
            np.testing.assert_array_equal(
                np.asarray(y),
                np.asarray(matmul.cim_matmul_int(x, w, cfg)),
            )
            with pytest.raises(ValueError, match="infeasible"):
                dispatch.dispatch(x, w, cfg, backend="boom")
        finally:
            dispatch._TABLE.pop(kk, None)

    def test_registered_variant_autowires_scan(self):
        """One variants.register() call is enough to execute — the
        dispatch half of 'one registration instead of three edits'."""
        var = dataclasses.replace(variants_lib.P8T, name="test-auto")
        variants_lib.register(var)
        try:
            cfg = PAPER_OP_16ROWS
            x, w = rand_codes(3, 32, 4, cfg)
            y = dispatch.dispatch(x, w, cfg, variant="test-auto")
            np.testing.assert_array_equal(
                np.asarray(y),
                np.asarray(matmul.cim_matmul_int(x, w, cfg)),
            )
            assert "scan" in dispatch.backends_for("test-auto")
            # auto-wiring must not squat the registration slot: an
            # explicit scan kernel for the variant still registers
            kk = dispatch.register_kernel(
                dispatch.KernelKey("test-auto", "scan"),
                lambda xc, wc, s, **kw: matmul.cim_matmul_int(xc, wc, s),
            )
            dispatch._TABLE.pop(kk, None)
        finally:
            variants_lib._VARIANTS.pop("test-auto", None)
            dispatch._TABLE.pop(
                dispatch.KernelKey("test-auto", "scan"), None
            )

    def test_shape_specialized_registration_wins(self):
        cfg = PAPER_OP_16ROWS
        x, w = rand_codes(2, 16, 2, cfg)
        cell = dispatch.shape_cell(2, 16, 2)
        marker = {}

        def special(xc, wc, spec, *, key=None, planes=None, block=None):
            marker["hit"] = True
            return matmul.cim_matmul_int(xc, wc, spec)

        key = dispatch.register_kernel(
            dispatch.KernelKey("p8t", "scan", cell), special,
        )
        try:
            dispatch.dispatch(x, w, cfg, backend="scan")
            assert marker.get("hit")
        finally:
            dispatch._TABLE.pop(key, None)

    def test_engine_backends_route_through_dispatch(self):
        """'behavioral'/'pallas' engine backends resolve in the table.

        The behavioral mode at a decode shape (m=4) rides the plan's
        spread-slot operand via the heuristic — still dispatch-routed."""
        cfg = PAPER_OP_16ROWS
        w = jnp.asarray(RNG.normal(size=(64, 8)) * 0.1, jnp.float32)
        x = jnp.asarray(RNG.normal(size=(4, 64)).clip(-3, 3), jnp.float32)
        for mode, backend in [("cim", "slots"), ("cim-kernel", "pallas")]:
            policy = CIMPolicy(mode=mode, cim=cfg, ste=False)
            plan = engine.plan_weights(w, cfg, policy)
            with dispatch.record_resolutions() as log:
                engine.execute(x, plan, policy)
            assert log and log[0].key.backend == backend, mode

    def test_calibrated_backend_routes_through_dispatch(self):
        w = jnp.asarray(RNG.normal(size=(32, 8)) * 0.1, jnp.float32)
        x = jnp.asarray(RNG.normal(size=(16, 32)).clip(0, 3), jnp.float32)
        res = cal.calibrate(
            default_pipeline(), {"l": w}, {"l": x},
            cal.CalibrationGrid(adc_bits=(4,), rows_active=(16,),
                                coarse_bits=(1,),
                                variants=("adder-tree",)),
            noisy=False,
        )
        name = res.register("dispatch-route-test")
        try:
            policy = CIMPolicy(mode="cim", backend=name,
                               cim=PAPER_OP_16ROWS, act_symmetric=True)
            plan = engine.plan_weights(w, policy.cim, policy)
            with dispatch.record_resolutions() as log:
                engine.execute(x, plan, policy)
            assert log and log[0].key.variant == "adder-tree"
        finally:
            engine._BACKENDS.pop("dispatch-route-test", None)


class TestDefaultBlock:
    """The Pallas tile follows the call's M when no block is pinned."""

    @pytest.mark.parametrize("variant", VARIANTS)
    @pytest.mark.parametrize("m", [1, 8, 32, 40, 128, 200])
    def test_default_block_bit_exact(self, variant, m):
        """At every M tile the output equals the (128, 128, 128) tile's
        and the variant's oracle: only padding rows stop being computed.
        k is not a multiple of 128, n not of the widened bn."""
        cfg = PAPER_OP_16ROWS
        k, n = 200, 300
        x, w = rand_codes(m, k, n, cfg)
        _, bn, _ = dispatch._pallas_blocks(dispatch.as_spec(cfg), None, m, n)
        assert n % bn, bn  # the last N tile is ragged at every bn
        default = dispatch.dispatch(x, w, cfg, variant=variant,
                                    backend="pallas")
        full = dispatch.dispatch(x, w, cfg, variant=variant,
                                 backend="pallas", block=(128, 128, 128))
        want = np.asarray(scan_oracle(variant, x, w, cfg))
        np.testing.assert_array_equal(np.asarray(default),
                                      np.asarray(full))
        np.testing.assert_array_equal(np.asarray(default), want)

    @pytest.mark.parametrize("rows", [8, 12, 16])
    def test_block_rule(self, rows):
        spec = dispatch.as_spec(PAPER_OP_16ROWS.replace(rows_active=rows))
        for m in (1, 2, 7, 8, 9, 31, 32, 33, 40, 64, 100, 127, 128, 129,
                  200, 2048, 4096):
            for n in (1, 100, 128, 300, 896, 4864):
                bm, bn, bk = dispatch._pallas_blocks(spec, None, m, n)
                assert bm == (128 if m >= 128 else -(-m // 8) * 8), (m, n)
                assert bk % rows == 0 and bk <= 128, (m, n)
                assert bm * bn <= 128 * 128, (m, n)
                assert bn % 128 == 0 and bn <= -(-n // 128) * 128, (m, n)
                if m >= 128:
                    assert bn == 128, (m, n)

    def test_explicit_block_wins(self):
        spec = dispatch.as_spec(PAPER_OP_16ROWS)
        assert dispatch._pallas_blocks(spec, (64, 128, 128), 32, 896) == (
            64, 128, 128)
        # bk still rounds down to a multiple of rows_active
        assert dispatch._pallas_blocks(spec, (8, 128, 100), 1, 896) == (
            8, 128, 96)

    def test_logged_block_is_the_block_that_ran(self, monkeypatch):
        """Under record_resolutions the Pallas route logs the block the
        kernel ran at: the rule's, an explicit one, a tuned pin's, and
        on the heuristic route (the chip's) the rule's again."""
        from repro.kernels import ops

        ran = []

        def spy(x_codes, w_codes, cfg, *, bm, bn, bk):
            ran.append((bm, bn, bk))
            return jnp.zeros((x_codes.shape[0], w_codes.shape[1]),
                             jnp.float32)

        monkeypatch.setattr(ops, "cim_matmul_kernel", spy)
        cfg = PAPER_OP_16ROWS
        x, w = rand_codes(32, 64, 896, cfg)
        cell = dispatch.shape_cell(32, 64, 896)
        rule = (32, 512, 128)
        cases = [
            (dict(backend="pallas"), "explicit", rule),
            (dict(backend="pallas", block=(16, 256, 64)), "explicit",
             (16, 256, 64)),
        ]
        for kwargs, source, want in cases:
            with dispatch.record_resolutions() as log:
                dispatch.dispatch(x, w, cfg, **kwargs)
            assert [(r.source, r.block) for r in log] == [(source, want)]
            assert ran[-1] == want
        cache = autotune.TuningCache(arch="test")
        cache.put("p8t", cell, autotune.Winner("pallas", (8, 128, 128), 1.0))
        autotune.set_active(cache)
        with dispatch.record_resolutions() as log:
            dispatch.dispatch(x, w, cfg)
        assert [(r.source, r.block) for r in log] == [
            ("tuned", (8, 128, 128))]
        assert ran[-1] == (8, 128, 128)
        autotune.clear_active()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with dispatch.record_resolutions() as log:
            dispatch.dispatch(x, w, cfg)
        assert [(r.source, r.key.backend, r.block) for r in log] == [
            ("heuristic", "pallas", rule)]
        assert ran[-1] == rule


class TestAutotune:
    def fake_measure(self, order):
        def measure(cand, run):
            run()
            # backends the order doesn't rank (e.g. "slots") never win
            return float(order.get(cand[0], 99.0))

        return measure

    def test_sweep_deterministic(self):
        meas = self.fake_measure({"scan": 2.0, "ref": 1.0, "pallas": 3.0})
        w1 = autotune.sweep_shape("p8t", PAPER_OP_16ROWS, 4, 64, 8,
                                  measure=meas)
        w2 = autotune.sweep_shape("p8t", PAPER_OP_16ROWS, 4, 64, 8,
                                  measure=meas)
        assert w1 == w2
        assert w1.backend == "ref"

    def test_cache_round_trip(self, tmp_path):
        meas = self.fake_measure({"scan": 1.0, "ref": 2.0, "pallas": 3.0})
        path = tmp_path / "testarch.json"
        cache = autotune.autotune(
            [(4, 64, 8), (32, 128, 16)], PAPER_OP_16ROWS,
            variants=VARIANTS, measure=meas, path=path, activate=False,
            merge=False,
        )
        loaded = autotune.TuningCache.load(path=path)
        assert loaded.to_json() == cache.to_json()
        # same sweep -> byte-identical file (pinned-winner determinism)
        cache2 = autotune.autotune(
            [(4, 64, 8), (32, 128, 16)], PAPER_OP_16ROWS,
            variants=VARIANTS, measure=meas, save=False, activate=False,
            merge=False,
        )
        assert cache2.to_json()["entries"] == cache.to_json()["entries"]

    def test_cache_version_guard(self, tmp_path):
        path = tmp_path / "bad.json"
        path.write_text(json.dumps({"version": 99, "entries": {}}))
        with pytest.raises(ValueError, match="version"):
            autotune.TuningCache.load(path=path)

    def test_missing_cache_heuristic_fallback_one_time_log(
        self, tmp_path, monkeypatch, caplog
    ):
        """No results/autotune/<arch>.json: dispatch degrades to the
        deterministic heuristics with exactly one log line naming the
        missing file (never re-logged, never an error)."""
        monkeypatch.setenv("REPRO_AUTOTUNE_DIR", str(tmp_path))
        with caplog.at_level(logging.INFO,
                             logger="repro.kernels.autotune"):
            assert autotune.reload_active() is None
            assert autotune.active_cache() is None  # cached; no re-log
            assert autotune.lookup(
                "p8t", dispatch.shape_cell(4, 64, 8)) is None
        msgs = [r.getMessage() for r in caplog.records
                if "no tuning cache" in r.getMessage()]
        assert len(msgs) == 1, msgs
        assert str(tmp_path) in msgs[0]
        cfg = PAPER_OP_16ROWS
        x, w = rand_codes(4, 64, 8, cfg)
        with dispatch.record_resolutions() as log:
            y = dispatch.dispatch(x, w, cfg)
        assert log[0].source == "heuristic"
        np.testing.assert_array_equal(
            np.asarray(y),
            np.asarray(matmul.cim_matmul_int(x, w, cfg)),
        )

    def test_infeasible_candidates_skipped(self):
        """A candidate that raises (depth guard etc.) is never a winner."""
        def boom(xc, wc, spec, *, key=None, planes=None, block=None):
            raise dispatch.KernelInfeasible("infeasible")

        key = dispatch.register_kernel(
            dispatch.KernelKey("p8t", "boom"), boom
        )
        try:
            win = autotune.sweep_shape(
                "p8t", PAPER_OP_16ROWS, 4, 64, 8,
                candidates=(("boom", None), ("scan", None)),
                measure=self.fake_measure({"scan": 1.0, "boom": 0.0}),
            )
            assert win.backend == "scan"
        finally:
            dispatch._TABLE.pop(key, None)

    def test_tuned_execution_bit_exact(self):
        """Pinning a different backend never changes the result."""
        cfg = PAPER_OP_16ROWS
        x, w = rand_codes(8, 256, 32, cfg)
        base = np.asarray(dispatch.dispatch(x, w, cfg, backend="scan"))
        cache = autotune.TuningCache(arch="test")
        cache.put("p8t", dispatch.shape_cell(8, 256, 32),
                  autotune.Winner("ref", None, 1.0))
        autotune.set_active(cache)
        np.testing.assert_array_equal(
            np.asarray(dispatch.dispatch(x, w, cfg)), base
        )


class TestCalibratedPlanGrouping:
    """Satellite: plan_params(calibration=) pre-groups planes at each
    layer's calibrated rows_active — the traced regroup_planes reshape
    must never run for such plans."""

    @pytest.fixture()
    def calibrated(self):
        w = jnp.asarray(RNG.normal(size=(48, 8)) * 0.1, jnp.float32)
        x = jnp.asarray(RNG.normal(size=(32, 48)).clip(0, 3), jnp.float32)
        res = cal.calibrate(
            default_pipeline(), {"l": w}, {"l": x},
            cal.CalibrationGrid(adc_bits=(4,), rows_active=(8,),
                                coarse_bits=(1,)),
            noisy=False,
        )
        assert res.layers["l"].spec.rows_active == 8
        return w, x, res

    @pytest.mark.parametrize("pack", [False, True],
                             ids=["unpacked", "packed"])
    def test_planes_pre_grouped_no_regroup(self, calibrated, monkeypatch,
                                           pack):
        w, x, res = calibrated
        name = res.register("plan-group-test")
        try:
            policy = CIMPolicy(mode="cim", backend=name,
                               cim=PAPER_OP_16ROWS, act_symmetric=True)
            plan = engine.plan_weights(
                w, policy.cim, policy, with_planes=True,
                pack_planes=pack,
                group_rows=res.layers["l"].spec.rows_active,
            )
            assert plan.planes.shape[-2] == 8  # calibrated, not cfg's 16
            called = []
            real = engine.regroup_planes
            monkeypatch.setattr(
                engine, "regroup_planes",
                lambda *a, **k: (called.append(1), real(*a, **k))[1],
            )
            y = engine.execute(x, plan, policy)
            assert not called, "regroup ran despite calibrated grouping"
            # parity with the plan-time-16 / regroup-at-trace path
            plan16 = engine.plan_weights(w, policy.cim, policy,
                                         with_planes=True,
                                         pack_planes=pack)
            y16 = engine.execute(x, plan16, policy)
            np.testing.assert_array_equal(np.asarray(y), np.asarray(y16))
        finally:
            engine._BACKENDS.pop("plan-group-test", None)

    def test_behavioral_policy_regroups_calibration_grouped_plan(
        self, calibrated
    ):
        """A calibration-grouped plan must stay executable under a
        plain behavioral policy (planes reflow to the policy's rows
        instead of failing deep inside the kernel)."""
        w, x, res = calibrated
        policy = CIMPolicy(mode="cim", cim=PAPER_OP_16ROWS,
                           act_symmetric=True)
        plan8 = engine.plan_weights(w, policy.cim, policy,
                                    with_planes=True, group_rows=8)
        plan16 = engine.plan_weights(w, policy.cim, policy,
                                     with_planes=True)
        np.testing.assert_array_equal(
            np.asarray(engine.execute(x, plan8, policy)),
            np.asarray(engine.execute(x, plan16, policy)),
        )

    def test_plan_params_consumes_calibration(self, calibrated):
        w, _, res = calibrated
        policy = CIMPolicy(mode="cim", cim=PAPER_OP_16ROWS,
                           act_symmetric=True)
        tree = engine.plan_params({"w": w}, policy.cim, policy,
                                  calibration=res)
        assert tree["w"].planes.shape[-2] == 8
        # dry-run tree mirrors the calibrated grouping structurally
        sds = jax.eval_shape(lambda: {"w": w})
        t_sds = engine.plan_params(sds, policy.cim, policy,
                                   calibration=res)
        assert t_sds["w"].planes.shape == tree["w"].planes.shape
