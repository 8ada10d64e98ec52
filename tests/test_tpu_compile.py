"""Deviceless TPU v5e compiles of the main path's Pallas kernels.

The TPU compiler is installed even where no chip is attached: it
compiles for a *described* v5e topology, and refuses what the chip
would refuse (unsupported Mosaic lowerings, misaligned blocks, captured
constants) — none of which interpret mode can see. Each test compiles
one kernel with ``interpret=False`` and asserts that the program holds
the Mosaic kernel (``tpu_custom_call``).

This is the only file that describes a topology, and it does so inside
the ``topo`` fixture: describing one loads the TPU library, which one
process at a time may hold, so nothing here may run at import, in a
``skipif`` or in a ``parametrize`` argument (every test worker imports
every test file).

The last test is a CPU test of the same concern from the dispatch
side: an error that is not ``KernelInfeasible`` (what a kernel the
compiler refuses raises) must propagate, not fall back to the scan.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    AxisType,
    Mesh,
    NamedSharding,
    PartitionSpec,
    SingleDeviceSharding,
)

from repro.core import matmul
from repro.core.params import PAPER_OP_8ROWS, PAPER_OP_16ROWS
from repro.kernels import autotune, cim_mac, dispatch, ops

KERNELS = {
    "p8t": cim_mac.gpq_matmul,
    "adder-tree": cim_mac.adder_tree_gpq_matmul,
    "cell-adc": cim_mac.cell_adc_gpq_matmul,
}
# qwen2-0.5b projections: prefill m=128 on gate/up (896->4864, i8
# codes), decode m=1 on q/o (896->896) and m=8 on the packed down
# projection (4864->896, u8 plane bytes) at every decode_blocks tiling
# offered for that m, and the served batch-32 decode step on gate/up
# and down at the default block, the one dispatch runs when no block
# is pinned (``dispatch._pallas_blocks``; prefill's is (128, 128, 128)).
LM_CASES = {
    "prefill-m128-896x4864": (128, 896, 4864, jnp.int8, False),
    "decode-m1-896x896": (1, 896, 896, jnp.int8, True),
    "decode-m8-4864x896": (8, 4864, 896, jnp.uint8, True),
    "decode-m32-896x4864": (32, 896, 4864, jnp.int8, False),
    "decode-m32-4864x896": (32, 4864, 896, jnp.uint8, False),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A deviceless compile can be written to the persistent cache but
    # never read back without a chip: keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("case", sorted(LM_CASES))
@pytest.mark.parametrize("variant", sorted(KERNELS))
def test_kernel_compiles_at_lm_widths(one_chip, variant, case):
    m, k, n, wdtype, decode = LM_CASES[case]
    spec = dispatch.as_spec(PAPER_OP_16ROWS)
    blocks = (
        autotune.decode_blocks(spec.rows_active, m) if decode
        else (dispatch._pallas_blocks(spec, None, m, n),)
    )
    x = jax.ShapeDtypeStruct((m, k), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), wdtype, sharding=one_chip)
    for bm, bn, bk in blocks:
        hlo = _compile(
            lambda xx, ww, _bm=bm, _bn=bn, _bk=bk: KERNELS[variant](
                xx, ww, PAPER_OP_16ROWS, bm=_bm, bn=_bn, bk=_bk,
                interpret=False,
            ),
            x, w,
        )
        assert "tpu_custom_call" in hlo, (variant, case, (bm, bn, bk))


def test_gpq_compiles_at_resnet_im2col(one_chip):
    """ResNet-20 stage 0 at the paper point: 256 images of 32x32
    patches, K = 3*3*16 = 144, N = 16, rows_active = 8."""
    x = jax.ShapeDtypeStruct((256 * 32 * 32, 144), jnp.int32,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((144, 16), jnp.int8, sharding=one_chip)
    hlo = _compile(
        lambda xx, ww: cim_mac.gpq_matmul(
            xx, ww, PAPER_OP_8ROWS, interpret=False
        ),
        x, w,
    )
    assert "tpu_custom_call" in hlo


def test_kernel_compiles_per_device_under_a_mesh(topo, monkeypatch):
    """XLA cannot partition a Mosaic kernel: on a 4-chip mesh it runs
    per device in a shard_map over the weight columns, as sharded
    serving (``ServeEngine(mesh=)``) runs it."""
    # ops decides native lowering from the backend, which is the CPU
    # here; steer it to the chip's branch for this compile only.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cols = PartitionSpec(None, "model")
    x = jax.ShapeDtypeStruct((8, 896), jnp.int32,
                             sharding=NamedSharding(mesh, PartitionSpec()))
    w = jax.ShapeDtypeStruct((896, 4864), jnp.int8,
                             sharding=NamedSharding(mesh, cols))
    per_device = jax.shard_map(
        lambda xx, ww: ops.cim_matmul_kernel(xx, ww, PAPER_OP_16ROWS),
        mesh=mesh, in_specs=(PartitionSpec(), cols), out_specs=cols,
        check_vma=False,
    )
    assert "tpu_custom_call" in _compile(per_device, x, w)


def test_non_infeasible_error_propagates_from_implicit_dispatch():
    """Only KernelInfeasible falls back to the scan: any other error of
    an implicitly chosen implementation (a kernel the device compiler
    refuses raises ValueError or NotImplementedError) surfaces."""
    def refused(xc, wc, spec, *, key=None, planes=None, block=None):
        raise ValueError("block shape not divisible by (8, 128)")

    kk = dispatch.register_kernel(dispatch.KernelKey("p8t", "refused"),
                                  refused)
    cfg = PAPER_OP_16ROWS
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, cfg.act_levels, (3, 32)), jnp.int32)
    w = jnp.asarray(rng.integers(-128, 128, (32, 4)), jnp.int32)
    cache = autotune.TuningCache(arch="test")
    cache.put("p8t", dispatch.shape_cell(3, 32, 4),
              autotune.Winner("refused", None, 1.0))
    autotune.set_active(cache)
    try:
        with dispatch.record_resolutions() as log:
            with pytest.raises(ValueError, match="divisible") as err:
                dispatch.dispatch(x, w, cfg)
        assert not isinstance(err.value, dispatch.KernelInfeasible)
        assert [r.source for r in log] == ["tuned"]
        # The same pin raising KernelInfeasible still falls back, loudly.
        def infeasible(xc, wc, spec, *, key=None, planes=None, block=None):
            raise dispatch.KernelInfeasible("too deep")

        dispatch.register_kernel(dispatch.KernelKey("p8t", "refused"),
                                 infeasible, overwrite=True)
        with dispatch.record_resolutions() as log:
            y = dispatch.dispatch(x, w, cfg)
        assert [r.source for r in log] == ["tuned", "guard-fallback"]
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(matmul.cim_matmul_int(x, w, cfg))
        )
    finally:
        autotune.clear_active()
        dispatch._TABLE.pop(kk, None)
