"""Work a step needs, counted from its shapes.

One module for both kinds of share the benchmark reports:

* ``mfu.*``: nominal FLOPs of the plain model that the macro simulates
  (2 x multiply-accumulates of every weight matmul, the LM head and the
  attention core), against the chip's bf16 peak. It counts the model,
  not the bit-plane work, so it bounds a gain whatever implements the
  macro.
* ``gpq_roofline.*``: operations and bytes the macro matmul needs at
  its unpadded call shape, whatever kernel implements it: 2*M*K*N per
  weight bit plane (4-bit codes against 0/1 planes, so against the int8
  peak), and int8 activation codes + int8 weight codes (packed planes
  are one byte per weight) + the f32 result through HBM.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class LMShape:
    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "LMShape":
        """From a configuration file's Hugging Face keys."""
        heads = c["num_attention_heads"]
        return cls(
            layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            n_heads=heads, n_kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim") or c["hidden_size"] // heads,
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        )

    def projections(self) -> list[tuple[str, int, int]]:
        """(name, K, N) of every weight matmul of one layer."""
        d, q, kv, f = (self.d_model, self.n_heads * self.head_dim,
                       self.n_kv_heads * self.head_dim, self.d_ff)
        return [("q", d, q), ("k", d, kv), ("v", d, kv), ("o", q, d),
                ("gate", d, f), ("up", d, f), ("down", f, d)]

    def layer_params(self) -> int:
        return sum(k * n for _, k, n in self.projections())


def lm_flops(shape: LMShape, batch: int, q_positions, logits_rows: int
             ) -> float:
    """Nominal FLOPs of one forward over ``q_positions`` (each row of
    the batch holds queries at these absolute positions, causal) that
    produces logits for ``logits_rows`` rows."""
    n_q = len(q_positions)
    ctx = sum(p + 1 for p in q_positions)
    matmul = 2.0 * shape.layers * shape.layer_params() * batch * n_q
    attn = (4.0 * shape.layers * shape.n_heads * shape.head_dim
            * batch * ctx)
    head = 2.0 * shape.d_model * shape.vocab * logits_rows
    return matmul + attn + head


def lm_generate_flops(shape: LMShape, batch: int, prompt: int,
                      new_tokens: int) -> dict:
    """FLOPs of ``generate``: one prefill (logits at the last position
    only) and ``new_tokens - 1`` single-token decode steps."""
    prefill = lm_flops(shape, batch, range(prompt), batch)
    decode = sum(lm_flops(shape, batch, [prompt + i], batch)
                 for i in range(new_tokens - 1))
    return {"prefill": prefill, "decode": decode, "total": prefill + decode}


def resnet_macs_per_image(widths, blocks_per_stage: int, n_classes: int,
                          hw: int = 32, in_ch: int = 3) -> int:
    """MACs of one CIFAR ResNet forward: 3x3 stem, basic blocks with a
    stride-2 first conv and a 1x1 projection where the width changes,
    global average pool, fc."""
    macs = hw * hw * 9 * in_ch * widths[0]
    cin, size = widths[0], hw
    for si, cout in enumerate(widths):
        for bi in range(blocks_per_stage):
            if bi == 0 and si > 0:
                size //= 2
            macs += size * size * 9 * cin * cout  # conv1
            macs += size * size * 9 * cout * cout  # conv2
            if cin != cout:
                macs += size * size * cin * cout  # 1x1 projection
            cin = cout
    return macs + cin * n_classes


def gpq_work(m: int, k: int, n: int, weight_bits: int) -> tuple[float, float]:
    """(operations, bytes) one macro matmul needs at its unpadded shape."""
    ops = 2.0 * m * k * n * weight_bits
    nbytes = float(m * k + k * n + 4 * m * n)
    return ops, nbytes


def roofline_seconds(ops: float, nbytes: float, peaks: dict
                     ) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_ops = ops / peaks["int8_ops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
