"""Tiny configurations and traffic for running cells on the CPU."""

import argparse
import copy
import json

from perfbench import harness

CPU_PEAKS = {"source": "test", "bf16_flops": 1e12, "int8_ops": 2e12,
             "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def lm_config() -> dict:
    c = harness.load_json(harness.HERE / "configs" / "qwen2-0.5b-cim16.json")
    c = copy.deepcopy(c)
    c["model"].update(hidden_size=64, intermediate_size=128,
                      num_attention_heads=4, num_key_value_heads=2,
                      num_hidden_layers=2, vocab_size=512,
                      max_position_embeddings=64)
    return c


def lm_traffic(kind: str) -> dict:
    name = {"decode": "lm_decode_b32", "prefill": "lm_prefill_b4x1024"}[kind]
    t = copy.deepcopy(harness.load_json(harness.HERE / "traffic"
                                        / f"{name}.json"))
    if kind == "decode":
        t.update(batch=4, prompt_len=8, new_tokens=6, max_len=16,
                 distinct_batches=2)
    else:
        t.update(batch=2, prompt_len=16, new_tokens=1, max_len=16,
                 distinct_batches=2)
    t["limits"] = {"logit_gap": 1e-3}
    return t


def resnet_config() -> dict:
    c = copy.deepcopy(harness.load_json(
        harness.HERE / "configs" / "resnet20-cifar-paper.json"))
    c.update(widths=[8, 16, 32], blocks_per_stage=1)
    return c


def resnet_traffic() -> dict:
    t = copy.deepcopy(harness.load_json(harness.HERE / "traffic"
                                        / "cifar_b1024.json"))
    t.update(batch=8, distinct_batches=2, trace_calls=2)
    t["limits"] = {"logit_err": 1e-3}
    return t


def run(workload: str, config: dict, traffic: dict, *, seed=2**33 + 5,
        seconds=0.0, trace=0):
    """One run of a cell on the CPU (the chip check skipped)."""
    import jax
    import time

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
    result, checks = harness.execute(bench, args, config, traffic,
                                     jax.devices()[:1], CPU_PEAKS,
                                     time.perf_counter())
    json.dumps(result)
    return result, checks
