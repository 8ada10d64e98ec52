"""Work counts against shapes worked by hand."""

import pytest

from perfbench import work

PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
QWEN = {"num_hidden_layers": 24, "hidden_size": 896,
        "num_attention_heads": 14, "num_key_value_heads": 2,
        "intermediate_size": 4864, "vocab_size": 151936}


def test_resnet20_macs():
    # stem 32*32*27*16; stage 0: 6 convs of 32*32*144*16; stages 1 and 2:
    # a strided conv1, conv2, a 1x1 projection and 4 more convs each
    # (13,107,200 MACs per stage); fc 64*10.
    assert work.resnet_macs_per_image((16, 32, 64), 3, 10) == 40_813_184


def test_qwen2_layer_params():
    s = work.LMShape.from_config(QWEN)
    assert s.head_dim == 64
    assert s.layer_params() == 14_909_440
    assert [p[0] for p in s.projections()] == [
        "q", "k", "v", "o", "gate", "up", "down"]


def test_lm_flops_by_hand():
    s = work.LMShape(layers=2, d_model=8, n_heads=2, n_kv_heads=1,
                     head_dim=4, d_ff=16, vocab=10)
    # per layer: q 8*8 + k,v 2*8*4 + o 8*8 + 3*8*16 = 576 weights
    assert s.layer_params() == 576
    # batch 3, queries at positions 0, 1 (context 1 + 2), logits for 3 rows
    got = work.lm_flops(s, 3, [0, 1], 3)
    want = 2 * 2 * 576 * 3 * 2 + 4 * 2 * 2 * 4 * 3 * 3 + 2 * 8 * 10 * 3
    assert got == want


def test_generate_flops_split():
    s = work.LMShape.from_config(QWEN)
    f = work.lm_generate_flops(s, 32, 64, 192)
    assert f["prefill"] == work.lm_flops(s, 32, range(64), 32)
    assert f["decode"] == sum(work.lm_flops(s, 32, [64 + i], 32)
                              for i in range(191))
    assert f["total"] == f["prefill"] + f["decode"]


def test_gpq_work_decode_gate():
    ops, nbytes = work.gpq_work(32, 896, 4864, 8)
    assert ops == 2 * 32 * 896 * 4864 * 8 == 2_231_369_728
    assert nbytes == 32 * 896 + 896 * 4864 + 4 * 32 * 4864 == 5_009_408
    t, bound = work.roofline_seconds(ops, nbytes, PEAKS)
    assert bound == "bytes"
    assert t == pytest.approx(5_009_408 / 819e9)


def test_gpq_work_prefill_is_ops_bound():
    t, bound = work.roofline_seconds(*work.gpq_work(4096, 896, 4864, 8),
                                     PEAKS)
    assert bound == "ops"
    assert t == pytest.approx(2 * 4096 * 896 * 4864 * 8 / 393e12)
