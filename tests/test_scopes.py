"""The device scopes by which a profiler trace attributes the program's
operations (``perfbench/scopes.py`` reads them from each op's
``op_name`` in the optimized HLO): every scope is found in the compiled HLO of the serving
steps and the ResNet forward at tiny sizes, so a refactor that drops or
renames one fails here and not on the chip."""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import CIMPolicy, ModelConfig
from repro.core import engine
from repro.core.params import CIMConfig, PAPER_OP_16ROWS
from repro.kernels import autotune, dispatch
from repro.models import resnet, transformer

CIM = CIMPolicy(mode="cim", cim=CIMConfig(rows_active=16, act_bits=4,
                                          weight_bits=8, adc_bits=4,
                                          cutoff=0.5))
LM = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                 qkv_bias=True, tie_embeddings=True, max_seq_len=64,
                 activation_dtype="bfloat16", cim=CIM)
ENGINE_SCOPES = {"cim.quantize", "cim.macro", "cim.epilogue"}
LM_SCOPES = ENGINE_SCOPES | {"attn.core", "attn.cache", "lm.norm", "lm.head"}
RESNET_SCOPES = ENGINE_SCOPES | {"resnet.im2col", "resnet.bn",
                                 "resnet.pool"}


@pytest.fixture(autouse=True)
def _no_ambient_tuning_cache():
    autotune.clear_active()
    yield
    autotune.clear_active()


def op_names(fn, *args) -> list[str]:
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', txt)


def scopes_in(names) -> set[str]:
    return {part for n in names for part in n.split("/")}


@pytest.fixture(scope="module")
def lm():
    params = engine.plan_params(
        transformer.init(jax.random.PRNGKey(0), LM), policy=LM.cim)
    # a cache longer than the prompt: one exactly as long is replaced
    # whole, and XLA then drops the write
    caches = transformer.init_caches(LM, 2, 32, dtype=jnp.bfloat16)
    return params, caches


def test_decode_step_scopes(lm):
    params, caches = lm
    names = op_names(
        lambda p, t, c: transformer.decode_step(p, t, jnp.int32(3), c, LM),
        params, jnp.zeros((2,), jnp.int32), caches)
    assert LM_SCOPES <= scopes_in(names)
    # the backend that ran names a sub-scope of the macro's
    assert any("/cim.macro/scan/" in n for n in names)


def test_prefill_scopes(lm):
    params, caches = lm
    names = op_names(lambda p, t, c: transformer.prefill(p, t, c, LM),
                     params, jnp.zeros((2, 16), jnp.int32), caches)
    assert LM_SCOPES <= scopes_in(names)


def test_resnet_forward_scopes():
    cfg = resnet.ResNetConfig(
        widths=(8, 16), blocks_per_stage=1,
        cim=CIMPolicy(mode="cim", cim=CIMConfig(rows_active=8, adc_bits=4,
                                                cutoff=0.5),
                      act_symmetric=True))
    params, bn = resnet.init(jax.random.PRNGKey(0), cfg)
    planned = resnet.plan_params(params, cfg.cim)
    names = op_names(lambda p, b, x: resnet.forward(p, b, x, cfg)[0],
                     planned, bn, jnp.zeros((2, 32, 32, 3)))
    assert RESNET_SCOPES <= scopes_in(names)
    assert any("/cim.macro/scan/" in n for n in names)


@pytest.mark.parametrize("backend", ["scan", "ref", "slots", "pallas"])
def test_dispatch_names_the_backend(backend):
    cfg = PAPER_OP_16ROWS
    w = jax.random.randint(jax.random.PRNGKey(1), (32, 8), -127, 128,
                           jnp.int32).astype(jnp.int8)
    plan = engine.plan_weights(w.astype(jnp.float32), cfg,
                               with_slots=backend == "slots")
    x = jnp.zeros((4, 32), jnp.int32)
    names = op_names(
        lambda x, p: dispatch.dispatch(x, p.codes, cfg, backend=backend,
                                       slots=p.slots), x, plan)
    assert any(n.split("/")[1:2] == [backend] for n in names), names
