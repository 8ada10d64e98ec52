"""Whole runs of each cell on the CPU at tiny sizes (the chip check
skipped): sound runs are correct, and a run whose timed path is broken
underneath is not. Plus the control: the reference one precision
lower, in the program's place, fails the cell's own limit."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from perfbench import harness
from perfbench.tests import tiny

jax.config.update("jax_enable_compilation_cache", False)


def test_no_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(harness.HERE / "run.py"), "--workload",
         "resnet20.paper", "--seed", str(2**32 + 3), "--seconds", "1",
         "--trace", "0"], cwd=harness.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


CELLS = {
    "decode": ("qwen2-0.5b.decode", tiny.lm_config, lambda: tiny.lm_traffic(
        "decode")),
    "prefill": ("qwen2-0.5b.prefill", tiny.lm_config,
                lambda: tiny.lm_traffic("prefill")),
    "resnet": ("resnet20.paper", tiny.resnet_config, tiny.resnet_traffic),
}


# The committed traffic file of each cell (its limits).
TRAFFIC = {"decode": "lm_decode_b32.json",
           "prefill": "lm_prefill_b4x1024.json",
           "resnet": "cifar_b1024.json"}


def _run(kind, **kw):
    name, config, traffic = CELLS[kind]
    return tiny.run(name, config(), traffic(), **kw)


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_sound_run_is_correct(kind):
    result, checks = _run(kind)
    assert result["correct"], result["checks"]
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert list(result)[-1] == "checks"
    assert "setup_s" in result["metrics"]


def _alter_tokens(monkeypatch, how):
    from repro.serve import engine

    orig = engine.ServeEngine.generate

    def broken(self, prompts, n):
        toks = np.array(orig(self, prompts, n))
        if how == "token":
            toks[0, -1] = (toks[0, -1] + 1) % self.cfg.vocab_size
        else:  # half of the batch left out: its rows copy the other half
            h = toks.shape[0] // 2
            toks[h:] = toks[:toks.shape[0] - h]
        return toks

    monkeypatch.setattr(engine.ServeEngine, "generate", broken)


def _alter_logits(monkeypatch, how):
    from repro.models import resnet

    orig = resnet.forward

    def broken(params, bn, x, cfg, **kw):
        if how == "answer":
            logits, st = orig(params, bn, x, cfg, **kw)
            return logits.at[0, 0].add(1.0), st
        h = x.shape[0] // 2
        logits, st = orig(params, bn, x[:h], cfg, **kw)
        return jax.numpy.concatenate([logits, logits[:x.shape[0] - h]]), st

    monkeypatch.setattr(resnet, "forward", broken)


@pytest.mark.parametrize("kind,how", [
    ("decode", "token"), ("decode", "half"), ("prefill", "token"),
    ("prefill", "half"), ("resnet", "answer"), ("resnet", "half")])
def test_broken_timed_path_is_not_correct(monkeypatch, kind, how):
    if kind == "resnet":
        _alter_logits(monkeypatch, how)
    else:
        _alter_tokens(monkeypatch, how)
    result, _ = _run(kind)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_control_fails_the_limit(kind):
    """The reference computed one precision lower (float8 for the LM's
    bfloat16, bfloat16 for ResNet's float32) reads above the cell's
    committed limit, on three seeds."""
    name, config, traffic = CELLS[kind]
    limits = harness.load_json(harness.HERE / "traffic" / TRAFFIC[kind])[
        "limits"]
    tr = traffic()
    drv = harness.load_module(harness.HERE / "drivers"
                              / f"{config()['driver']}.py")
    for seed in (5, 2**32 + 9, 2**31 - 1):
        cell = drv.setup(config(), tr, seed, jax.devices()[:1])
        samples = [(i, cell.call(i)) for i in range(tr["check_calls"])]
        cell.release()
        if kind == "resnet":
            assert cell.logit_err(samples) <= limits["logit_err"]
            assert cell.control_err(samples) > limits["logit_err"]
        else:
            gap, ctl = cell.gaps(samples, control=True)
            assert gap <= limits["logit_gap"]
            assert ctl > limits["logit_gap"]
