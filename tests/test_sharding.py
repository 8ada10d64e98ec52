"""Sharding rule-table tests.

These run on the single CPU device via a (1, 1)-shaped mesh carrying
the production axis NAMES -- spec_for decisions depend only on axis
names and divisibility, so the logic is fully testable without 512
devices (the dry-run exercises the real mesh).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ARCH_IDS, get_config
from repro.distributed import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.models import transformer


class FakeMesh:
    """Duck-typed mesh exposing .shape as a dict (all spec_for needs)."""

    def __init__(self, **shape):
        self.shape = shape


MESH1 = FakeMesh(data=16, model=16)
MESH2 = FakeMesh(pod=2, data=16, model=16)


class TestSpecFor:
    def test_tp_axes(self):
        spec = shd.spec_for(("embed", "mlp"), (1024, 4096), MESH1)
        assert spec == P("data", "model")

    def test_indivisible_degrades_to_replicated(self):
        # whisper kv_dim 384 heads=6: 6 not divisible by 16
        spec = shd.spec_for(("kv_heads",), (6,), MESH1)
        assert spec == P(None)

    def test_batch_uses_pod_and_data(self):
        spec = shd.spec_for(("batch", "seq"), (256, 4096), MESH2)
        assert spec == P(("pod", "data"), None)

    def test_no_axis_reuse_within_tensor(self):
        # both dims want 'model': only the first gets it
        rules = {"a": ("model",), "b": ("model",)}
        spec = shd.spec_for(("a", "b"), (16, 16), MESH1, rules)
        assert spec == P("model", None)

    def test_unknown_axis_is_replicated(self):
        spec = shd.spec_for((None, "nope"), (4, 4), MESH1)
        assert spec == P(None, None)


class TestKVCacheSpec:
    def test_divisible_heads_prefers_heads(self):
        # gemma3: kv=16 -> heads on model, seq on data (batch covers pod)
        spec = shd.kv_cache_spec((128, 32768, 16, 128), MESH1)
        assert spec == P("data", None, "model", None)

    def test_indivisible_heads_falls_back_to_seq(self):
        # qwen1.5: kv=20 indivisible -> cache seq takes the model axis
        spec = shd.kv_cache_spec((128, 32768, 20, 128), MESH1)
        assert spec == P("data", "model", None, None)

    def test_batch_one_long_context(self):
        # long_500k: batch unshardable; seq absorbs every idle axis
        spec = shd.kv_cache_spec((1, 524288, 8, 128), MESH2)
        assert spec == P(None, ("model", "pod", "data"), None, None)

    def test_leading_layers_dim_passthrough(self):
        spec = shd.kv_cache_spec((40, 128, 32768, 20, 128), MESH1)
        assert spec == P(None, "data", "model", None, None)


class TestArchDivisibility:
    """Every assigned arch's parameter tree must yield valid specs on
    the production mesh shapes (names + divisibility only)."""

    @pytest.mark.parametrize("arch", ARCH_IDS)
    @pytest.mark.parametrize("mesh", [MESH1, MESH2],
                             ids=["single", "multi"])
    def test_param_specs_valid(self, arch, mesh):
        cfg = get_config(arch)
        spec_tree = transformer.model_spec(cfg)
        axes = transformer.model_axes(cfg)

        def one(ax, sp):
            p = shd.spec_for(ax, sp.shape, mesh)
            # every named entry must divide
            for dim, entry in zip(sp.shape, p, strict=False):
                if entry is None:
                    continue
                names = entry if isinstance(entry, tuple) else (entry,)
                f = 1
                for nm in names:
                    f *= mesh.shape[nm]
                assert dim % f == 0, (arch, ax, sp.shape, p)

        jax.tree.map(one, axes, spec_tree,
                     is_leaf=lambda x: isinstance(x, tuple) and all(
                         a is None or isinstance(a, str) for a in x))

    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_vocab_dim_always_divides_model_axis(self, arch):
        cfg = get_config(arch)
        assert cfg.padded_vocab % 16 == 0


class TestActivationConstraints:
    def test_constrain_noop_without_mesh(self):
        x = jnp.ones((4, 8))
        y = shd.constrain(x, ("act_batch", "act_seq"))
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_constrain_under_real_mesh(self):
        mesh = make_host_mesh((1, 1))
        x = jnp.ones((4, 8, 16))

        @jax.jit
        def f(x):
            return shd.constrain(x, ("act_batch", "act_seq", "act_vocab"))

        with jax.set_mesh(mesh):
            y = f(x)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_cache_shardings_real_mesh_smoke(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        cfg = get_config("qwen2_0_5b", smoke=True)
        caches = jax.eval_shape(
            lambda: transformer.init_caches(cfg, 2, 32))
        sh = shd.cache_shardings(caches, mesh)
        assert all(
            s is None or hasattr(s, "spec")
            for s in jax.tree.leaves(sh, is_leaf=lambda x: x is None
                                     or hasattr(x, "spec"))
        )


class TestInferenceRules:
    def test_params_not_fsdp_sharded_for_inference(self):
        spec = shd.spec_for(("embed", "mlp"), (1024, 4096), MESH1,
                            shd.INFERENCE_RULES)
        assert spec == P(None, "model")

    def test_experts_ep_over_data_for_inference(self):
        spec = shd.spec_for(("experts", "embed", "mlp"),
                            (16, 8192, 24576), MESH1,
                            shd.INFERENCE_RULES)
        assert spec == P("data", None, "model")


class TestPlannedShardings:
    """Plan-aware serving: PlannedWeights leaves shard their
    output-channel dim over the model axis (packed AND unpacked
    planes); everything else replicates. Runs on the single CPU device
    via a (1, 1) mesh carrying the production axis names."""

    def _mesh(self):
        import numpy as np
        return Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model"))

    def test_plan_leaf_specs(self):
        from repro.core import engine as cim
        mesh = self._mesh()
        w = jnp.ones((64, 32), jnp.float32)
        for pack in (False, True):
            plan = cim.plan_weights(w, with_planes=True,
                                    pack_planes=pack, with_slots=True)
            sh = shd.plan_shardings(plan, mesh)
            assert sh.codes.spec == P(None, "model")
            assert sh.w.spec == P(None, "model")
            lead = (None,) * (plan.planes.ndim - 1)
            assert sh.planes.spec == P(*lead, "model")
            # Epilogue vectors and the slot-major slots stay whole.
            assert sh.scale.spec == P()
            assert sh.colsum.spec == P()
            assert sh.slots.spec == P()

    def test_tree_shardings_and_device_put(self):
        from repro.core import engine as cim
        mesh = self._mesh()
        tree = {
            "blk": {"w": jnp.ones((32, 16)), "bias": jnp.ones((16,))},
        }
        planned = cim.plan_params(tree, policy=None)
        sh = shd.planned_param_shardings(planned, mesh)
        assert sh["blk"]["w"].codes.spec == P(None, "model")
        assert sh["blk"]["bias"].spec == P()  # unplanned: replicated
        placed = shd.shard_planned(planned, mesh)
        got = placed["blk"]["w"].dequantized()
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(planned["blk"]["w"]
                                              .dequantized()))

    def test_no_mesh_is_noop(self):
        from repro.core import engine as cim
        planned = cim.plan_params({"w": jnp.ones((8, 4))}, policy=None)
        assert shd.planned_param_shardings(planned, None) is None
        assert shd.shard_planned(planned, None) is planned
