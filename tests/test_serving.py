"""Serving-path correctness: prefill + decode must reproduce the
training forward exactly (teacher-forced), for every cache type:
full KV, ring (sliding window), Mamba conv/ssm state, RWKV wkv state,
and the whisper encoder-decoder memory.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import transformer
from repro.serve.engine import ContinuousBatcher, Request, ServeEngine

ARCHS = ["qwen2_0_5b", "gemma3_27b", "rwkv6_1_6b", "jamba_1_5_large",
         "whisper_tiny", "qwen2_moe_a2_7b"]


def _setup(arch, B=2, S=24):
    cfg = get_config(arch, smoke=True).replace(
        activation_dtype="float32")
    if cfg.moe is not None:
        # Capacity-based grouped dispatch legitimately drops different
        # tokens in prefill (many tokens/group) vs decode (one token) --
        # exact phase equivalence requires the drop-free ragged path.
        import dataclasses
        cfg = cfg.replace(
            moe=dataclasses.replace(cfg.moe, dispatch="ragged"))
    key = jax.random.PRNGKey(7)
    params = transformer.init(key, cfg)
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    memory = None
    batch = {"tokens": toks, "labels": toks}
    if cfg.is_encoder_decoder:
        frames = 0.1 * jax.random.normal(
            key, (B, cfg.frontend_seq, cfg.d_model))
        batch["encoder_frames"] = frames
        memory = transformer.encode(params, frames, cfg, cfg.cim)
    return cfg, params, toks, batch, memory


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_then_decode_matches_forward(arch):
    B, S = 2, 24
    cfg, params, toks, batch, memory = _setup(arch, B, S)
    logits_full, _ = transformer.forward_train(params, batch, cfg)
    if cfg.frontend == "vision_patches":
        logits_full = logits_full[:, cfg.frontend_seq:]

    caches = transformer.init_caches(cfg, B, S + 4, dtype=jnp.float32)
    lg_pre, caches = transformer.prefill(params, toks[:, :-4], caches,
                                         cfg, memory=memory)
    np.testing.assert_allclose(
        np.asarray(lg_pre), np.asarray(logits_full[:, S - 5]),
        atol=5e-4, rtol=1e-3)
    for t in range(4):
        pos = jnp.asarray(S - 4 + t, jnp.int32)
        lg_dec, caches = transformer.decode_step(
            params, toks[:, S - 4 + t], pos, caches, cfg, memory=memory)
        np.testing.assert_allclose(
            np.asarray(lg_dec), np.asarray(logits_full[:, S - 4 + t]),
            atol=5e-4, rtol=1e-3, err_msg=f"{arch} step {t}")


def test_ring_cache_window_semantics():
    """Sliding-window layers: decode past the window must match a full
    forward (the ring keeps exactly the last `window` tokens)."""
    cfg = get_config("gemma3_27b", smoke=True).replace(
        activation_dtype="float32", window_size=8)
    B, S = 1, 20  # S > 2*window to exercise wraparound
    key = jax.random.PRNGKey(3)
    params = transformer.init(key, cfg)
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    logits_full, _ = transformer.forward_train(
        params, {"tokens": toks, "labels": toks}, cfg)

    caches = transformer.init_caches(cfg, B, S, dtype=jnp.float32)
    _, caches = transformer.prefill(params, toks[:, :4], caches, cfg)
    for t in range(4, S):
        lg, caches = transformer.decode_step(
            params, toks[:, t], jnp.asarray(t, jnp.int32), caches, cfg)
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(logits_full[:, -1]),
        atol=1e-3, rtol=1e-3)


def test_serve_engine_greedy_determinism():
    cfg = get_config("qwen2_0_5b", smoke=True)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    eng1 = ServeEngine(params, cfg, max_len=64, batch=2)
    eng2 = ServeEngine(params, cfg, max_len=64, batch=2)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    out1 = eng1.generate(prompts, 6)
    out2 = eng2.generate(prompts, 6)
    np.testing.assert_array_equal(out1, out2)
    assert out1.shape == (2, 6)
    assert out1.max() < cfg.vocab_size  # pad logits never win argmax


def test_continuous_batcher_completes_requests():
    cfg = get_config("qwen2_0_5b", smoke=True)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(params, cfg, max_len=64, batch=2)
    batcher = ContinuousBatcher(eng, eos_token=-1)  # no eos: run max_new
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 4),
                    max_new=3) for i in range(5)]
    for r in reqs:
        batcher.submit(r)
    done = batcher.run_until_done(max_ticks=200)
    assert len(done) == 5
    assert all(len(r.generated) == 3 for r in done)
    # 5 requests through 2 slots: continuous refill actually happened
    assert all(r.done for r in done)


def test_donated_and_sharded_plan_decode_parity():
    """Plan-aware serving invariants in one pass (engines are the
    expensive part — share them): plan-buffer donation must not change
    the token stream and must leave the caller's params intact (the
    engine owns a private copy); mesh= must shard the planned tree
    (planes over the model axis) and decode the same tokens."""
    from jax.sharding import Mesh

    cfg = get_config("qwen2_0_5b", smoke=True)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    don = ServeEngine(params, cfg, max_len=64, batch=2, plan=True,
                      donate_plan=True)
    ref = ServeEngine(params, cfg, max_len=64, batch=2, plan=True)
    sharded = ServeEngine(params, cfg, max_len=64, batch=2, plan=True,
                          mesh=mesh)
    out = don.generate(prompts, 6)
    np.testing.assert_array_equal(out, ref.generate(prompts, 6))
    np.testing.assert_array_equal(out, sharded.generate(prompts, 6))
    # the caller's tree survived the donations (engines copied it)
    jax.tree.map(lambda x: np.asarray(x).sum(), params)


_FOUR_DEVICE_SCRIPT = """
import sys
import jax, numpy as np
from repro.configs.base import CIMPolicy, get_config
from repro.core.engine import PlannedWeights
from repro.core.params import PAPER_OP_16ROWS
from repro.launch.mesh import make_host_mesh
from repro.models import transformer
from repro.serve.engine import ServeEngine

arch, must_split = sys.argv[1], sys.argv[2].split(",")
mesh = make_host_mesh((1, 4))
for policy in (CIMPolicy(mode="cim", cim=PAPER_OP_16ROWS), CIMPolicy()):
    cfg = get_config(arch, smoke=True).replace(
        activation_dtype="float32", cim=policy)
    if arch == "qwen2_0_5b":
        # 14 heads and 2 kv heads as in qwen2-0.5b: the head split does
        # not divide the 4-way model axis, only the weight columns do.
        cfg = cfg.replace(n_heads=14, n_kv_heads=2, d_model=224,
                          head_dim=16, d_ff=256, n_layers=6)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                 cfg.vocab_size)
    kw = dict(max_len=32, batch=4, plan=True)
    sharded = ServeEngine(params, cfg, mesh=mesh, **kw)
    one = ServeEngine(params, cfg, **kw)
    split = set()
    for path, p in jax.tree_util.tree_flatten_with_path(
            sharded.params,
            is_leaf=lambda x: isinstance(x, PlannedWeights))[0]:
        if isinstance(p, PlannedWeights) and len(
                {s.device for s in p.codes.addressable_shards}) == 4:
            split.add(jax.tree_util.keystr(path))
    for name in must_split:
        assert any(name in k for k in split), (name, sorted(split))
    caches = transformer.init_caches(cfg, 4, 32)
    ls = np.asarray(sharded._prefill(sharded.params, prompts, caches)[0])
    l1 = np.asarray(one._prefill(one.params, prompts, caches)[0])
    # every read of a plan yields whole columns: the one-device program
    # plus gathers, bit for bit
    np.testing.assert_array_equal(ls, l1)
    np.testing.assert_array_equal(sharded.generate(prompts, 4),
                                  one.generate(prompts, 4))
print("OK")
"""


@pytest.mark.parametrize("arch, must_split", [
    ("qwen2_0_5b", "['mlp']['gate']"),
    ("qwen2_moe_a2_7b", "['moe']['gate'],['moe']['down']"),
    ("jamba_1_5_large", "['moe']['up'],['x_proj'],['dt_proj']"),
])
def test_sharded_engine_matches_one_device_on_four_host_devices(
        arch, must_split):
    """``ServeEngine(mesh=)`` over a (1, 4) mesh of host devices (the
    CPU rehearsal of the four-chip path), for a dense, an MoE and a
    hybrid attention/mamba/MoE config: the named plans' weight columns
    sit on 4 devices, and the prefill logits and greedy tokens equal
    the one-device engine's bit for bit, under a CIM and an fp policy.
    A fresh process, since the host device count is fixed when JAX
    starts."""
    import os
    import pathlib
    import subprocess
    import sys

    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{src}{os.pathsep}{os.environ.get('PYTHONPATH', '')}")
    out = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICE_SCRIPT, arch, must_split],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("OK")
