"""Hypothesis property tests for the system's core invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="optional dep: pip install .[test]"
)
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import adc, dac, matmul, quant
from repro.core import variants as variants_lib
from repro.core.params import PAPER_OP_16ROWS, CIMConfig
from repro.core.pipeline import MacroSpec
from repro.kernels.ref import cim_matmul_ref

_SETTINGS = dict(max_examples=25, deadline=None)


@given(
    coarse=st.integers(0, 4),
    kappa=st.sampled_from([0.0, 0.5, 2.0]),
    vdd=st.sampled_from([0.6, 0.9, 1.2]),
)
@settings(**_SETTINGS)
def test_coarse_fine_split_equals_flat_flash_property(coarse, kappa, vdd):
    """Every coarse/fine split decodes every 4-bit code identically to
    the flat 15-comparator flash, across kappa and VDD."""
    cfg = PAPER_OP_16ROWS.replace(c_abl_ratio=kappa, vdd=vdd)
    pmac = jnp.arange(cfg.pmac_levels, dtype=jnp.float32)
    v = dac.abl_voltage_from_pmac(pmac, cfg)
    np.testing.assert_array_equal(
        np.asarray(adc.adc_read_voltage(v, cfg, coarse_bits=coarse)),
        np.asarray(adc.adc_flat_flash(v, cfg)),
    )


@given(
    rows=st.sampled_from([4, 8, 16]),
    adc_bits=st.integers(2, 5),
    data=st.data(),
)
@settings(**_SETTINGS)
def test_voltage_adc_monotone_under_noise_free_macrospec(
    rows, adc_bits, data
):
    """The voltage-domain coarse-fine transfer is monotone and bounded
    for every noise-free MacroSpec on the sweep grid."""
    try:
        spec = MacroSpec().replace(rows_active=rows, adc_bits=adc_bits,
                                   noisy=False)
    except ValueError:
        return  # bits out of range at this row count
    coarse = data.draw(st.integers(0, adc_bits))
    pmac = jnp.arange(spec.pmac_levels, dtype=jnp.float32)
    v = dac.abl_voltage_from_pmac(pmac, spec)
    try:
        codes = np.asarray(
            adc.adc_read_voltage(v, spec, coarse_bits=coarse)
        )
    except ValueError:
        return  # in-SRAM reference level not representable
    assert np.all(np.diff(codes) >= 0)
    assert codes.min() == 0 and codes.max() == spec.adc_codes - 1


@given(
    codes=st.lists(st.integers(0, 15), min_size=1, max_size=32),
    vdd=st.sampled_from([0.6, 0.9, 1.2]),
)
@settings(**_SETTINGS)
def test_dac_voltage_equation_property(codes, vdd):
    cfg = PAPER_OP_16ROWS.replace(vdd=vdd)
    x = jnp.asarray(codes, jnp.int32)
    v = np.asarray(dac.dac_voltage(x, cfg))
    want = (16 - np.asarray(codes)) / 16.0 * vdd
    np.testing.assert_allclose(v, want, rtol=1e-6)


@given(
    rows=st.sampled_from([4, 8, 16]),
    cutoff=st.sampled_from([0.0, 0.25, 0.5, 0.75]),
    adc_bits=st.integers(2, 6),
)
@settings(**_SETTINGS)
def test_adc_transfer_monotone_and_bounded(rows, cutoff, adc_bits):
    cfg = CIMConfig(rows_active=rows, cutoff=cutoff, adc_bits=adc_bits)
    pmac = jnp.arange(cfg.pmac_levels, dtype=jnp.float32)
    codes = np.asarray(adc.adc_transfer_int(pmac, cfg))
    assert np.all(np.diff(codes) >= 0)          # monotone
    assert codes.min() >= 0
    assert codes.max() <= cfg.adc_codes - 1     # bounded
    # dequantization never exceeds the clip threshold
    deq = np.asarray(adc.adc_dequant(jnp.asarray(codes), cfg))
    assert deq.max() <= cfg.threshold


@given(
    data=st.data(),
    bits=st.sampled_from([2, 4, 6, 8]),
)
@settings(**_SETTINGS)
def test_bitslice_roundtrip_property(data, bits):
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    vals = data.draw(
        st.lists(st.integers(lo, hi), min_size=1, max_size=64)
    )
    codes = jnp.asarray(vals, jnp.int32)
    back = quant.unslice_weights(quant.bitslice_weights(codes, bits), bits)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(vals))


@given(
    m=st.integers(1, 6),
    k_groups=st.integers(1, 4),
    n=st.integers(1, 5),
    seed=st.integers(0, 2**31 - 1),
)
@settings(**_SETTINGS)
def test_ref_equals_scan_property(m, k_groups, n, seed):
    cfg = PAPER_OP_16ROWS
    k = k_groups * cfg.rows_active
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, 16, (m, k)), jnp.int32)
    w = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int32)
    np.testing.assert_allclose(
        np.asarray(matmul.cim_matmul_int(x, w, cfg)),
        np.asarray(cim_matmul_ref(x, w, cfg)),
        atol=1e-3,
    )


@given(seed=st.integers(0, 2**31 - 1), cut_groups=st.integers(1, 3))
@settings(**_SETTINGS)
def test_group_locality_property(seed, cut_groups):
    """sum of shard-local GPQ matmuls == unsharded GPQ matmul, for any
    group-aligned K split (TP/EP exactness invariant)."""
    cfg = PAPER_OP_16ROWS
    rng = np.random.default_rng(seed)
    k = 4 * cfg.rows_active
    cut = cut_groups * cfg.rows_active
    x = jnp.asarray(rng.integers(0, 16, (3, k)), jnp.int32)
    w = jnp.asarray(rng.integers(-128, 128, (k, 2)), jnp.int32)
    full = matmul.cim_matmul_int(x, w, cfg)
    part = (matmul.cim_matmul_int(x[:, :cut], w[:cut], cfg)
            + matmul.cim_matmul_int(x[:, cut:], w[cut:], cfg))
    np.testing.assert_allclose(np.asarray(full), np.asarray(part),
                               atol=1e-3)


@given(
    rows=st.sampled_from([4, 8, 16]),
    adc_bits=st.integers(2, 5),
    data=st.data(),
)
@settings(**_SETTINGS)
def test_merged_single_adc_transfer_monotone_property(rows, adc_bits, data):
    """The adder-tree variant's merged single-ADC transfer is monotone
    and bounded for every noise-free spec on the sweep grid."""
    try:
        spec = MacroSpec().replace(rows_active=rows, adc_bits=adc_bits,
                                   noisy=False)
    except ValueError:
        return  # bits out of range at this row count
    mq = variants_lib.merged_quant(spec)
    lo = data.draw(st.integers(mq.m_min, mq.m_max - 1))
    hi = data.draw(st.integers(lo, mq.m_max))
    codes = np.asarray(variants_lib.merged_transfer_int(
        jnp.asarray([lo, hi], jnp.float32), spec))
    assert codes[0] <= codes[1]
    assert mq.code_min <= codes.min() and codes.max() <= mq.code_max


@given(
    vname=st.sampled_from(["p8t", "adder-tree", "cell-adc"]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=10, deadline=None)
def test_variant_pipeline_equals_oracle_property(vname, seed):
    """Every registered macro variant's voltage-domain pipeline matches
    its bit-exact integer oracle on random codes (noise off)."""
    var = variants_lib.get(vname)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, 16, 16), jnp.int32)
    w = jnp.asarray(rng.integers(-128, 128, (16, 8)), jnp.int32)
    spec = MacroSpec()
    state = var.pipeline.run(x, w, spec)
    np.testing.assert_array_equal(
        np.asarray(state.outputs), np.asarray(var.oracle_int(x, w, spec))
    )


@given(seed=st.integers(0, 2**31 - 1))
@settings(**_SETTINGS)
def test_quantize_acts_error_bound(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(8, 8)) * rng.uniform(0.1, 10),
                    jnp.float32)
    q = quant.quantize_acts(x, 4)
    err = np.abs(np.asarray(quant.dequantize_acts(q)) - np.asarray(x))
    assert err.max() <= float(np.asarray(q.scale).max()) * 0.5 + 1e-5


@given(seed=st.integers(0, 2**31 - 1))
@settings(**_SETTINGS)
def test_cim_error_bounded_by_quant_grid(seed):
    """End-to-end 'cim-exact' error vs fp is bounded by the two grids."""
    rng = np.random.default_rng(seed)
    cfg = PAPER_OP_16ROWS
    x = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 3)) * 0.2, jnp.float32)
    y = np.asarray(matmul.cim_matmul(x, w, cfg, mode="cim-exact",
                                     ste=False))
    y_fp = np.asarray(x @ w)
    qa = quant.quantize_acts(x.reshape(-1, 32), 4)
    qw = quant.quantize_weights(w, 8)
    k = 32
    # |err| <= K * (sx/2 * |w|max + sw/2 * |x|max + sx*sw/4)
    sx = float(np.asarray(qa.scale).max())
    sw = float(np.max(np.asarray(qw.scale)))
    bound = k * (0.5 * sx * float(jnp.max(jnp.abs(w)))
                 + 0.5 * sw * float(jnp.max(jnp.abs(x)))
                 + 0.25 * sx * sw) + 1e-4
    assert np.max(np.abs(y - y_fp)) <= bound


# ---------------------------------------------------------------------------
# PR 4: variant-aware kernel dispatch + autotune cache properties
# ---------------------------------------------------------------------------

from repro.kernels import autotune, dispatch  # noqa: E402


@given(
    variant=st.sampled_from(("p8t", "adder-tree", "cell-adc")),
    rows=st.sampled_from([8, 16]),
    m=st.integers(1, 10),
    k=st.integers(1, 80),
    n=st.integers(1, 10),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=10, deadline=None)
def test_every_registered_kernel_key_matches_oracle(
    variant, rows, m, k, n, seed
):
    """Pallas (interpret) / ref / scan parity for every registered
    KernelKey of every variant, across ragged shapes and row counts."""
    cfg = CIMConfig(rows_active=rows, cutoff=0.5, adc_bits=4)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, 16, (m, k)), jnp.int32)
    w = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int32)
    if variant == "adder-tree":
        want = variants_lib.adder_tree_matmul_int(x, w, cfg)
    else:
        want = matmul.cim_matmul_int(x, w, cfg)
    slots = quant.spread_slots(
        w, cfg.rows_active, cfg.act_bits, cfg.weight_bits
    )
    for backend in dispatch.backends_for(variant):
        got = dispatch.dispatch(x, w, cfg, variant=variant,
                                backend=backend, slots=slots)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want),
            err_msg=f"{variant}/{backend}",
        )


@given(
    t_scan=st.floats(0.1, 10.0),
    t_ref=st.floats(0.1, 10.0),
    t_slots=st.floats(0.1, 10.0),
    m=st.sampled_from([4, 8, 32]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=10, deadline=None)
def test_tuning_cache_round_trip_determinism(t_scan, t_ref, t_slots, m, seed):
    """Same sweep -> same pinned winners, and the JSON cache round-trips
    losslessly (the deterministic re-load path dispatch consults)."""
    del seed  # shapes/measure fully determine the sweep
    # One fake time per candidate backend default_candidates enumerates.
    times = {"scan": t_scan, "ref": t_ref, "slots": t_slots, "pallas": 99.0}

    def measure(cand, run):
        run()
        return times[cand[0]]

    kw = dict(
        variants=("p8t", "adder-tree"), measure=measure,
        save=False, activate=False, merge=False,
    )
    c1 = autotune.autotune([(m, 64, 8)], PAPER_OP_16ROWS, **kw)
    c2 = autotune.autotune([(m, 64, 8)], PAPER_OP_16ROWS, **kw)
    assert c1.to_json() == c2.to_json()
    rt = autotune.TuningCache.from_json(c1.to_json())
    assert rt.to_json() == c1.to_json()
    best = min(times, key=times.get)
    for win in c1.entries.values():
        assert win.backend == best


@given(
    variant=st.sampled_from(("p8t", "adder-tree", "cell-adc")),
    rows=st.sampled_from([4, 8, 16]),
    mode=st.sampled_from(["floor", "nearest"]),
    m=st.integers(1, 6),
    k=st.integers(1, 120),
    n=st.integers(1, 12),
    seed=st.integers(0, 2**31 - 1),
)
@settings(**_SETTINGS)
def test_fused_slots_equals_unfused_property(
    variant, rows, mode, m, k, n, seed
):
    """PR 9 tentpole invariant: the fused spread-slot formulation (one
    batched dot + field extraction) is bit-exact vs the unfused scan
    transfer for every variant, shape, row count and adc mode — the
    decode fast path never changes semantics."""
    cfg = CIMConfig(rows_active=rows, cutoff=0.5, adc_bits=4,
                    adc_mode=mode)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, cfg.act_levels, (m, k)), jnp.int32)
    w = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int32)
    slots = quant.spread_slots(
        w, cfg.rows_active, cfg.act_bits, cfg.weight_bits
    )
    if variant == "adder-tree":
        want = variants_lib.adder_tree_matmul_int(x, w, cfg)
    else:
        want = matmul.cim_matmul_int(x, w, cfg)
    got = dispatch.dispatch(
        x, w.astype(jnp.int8), cfg, variant=variant,
        backend="slots", slots=slots,
    )
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(want),
        err_msg=f"{variant}/slots rows={rows} mode={mode}",
    )
