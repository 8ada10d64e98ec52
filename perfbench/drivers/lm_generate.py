"""A language model served through ``ServeEngine.generate``.

The configuration file gives the model in its source's keys (Hugging
Face config.json) plus the macro's operating point; the traffic gives
the batch, the prompt length, the greedy tokens per request and the
cache length. A call is one ``generate`` of a whole batch, closed loop.
Weights are made by this driver from the seed, on the device, in one
jitted call, in the program's parameter layout; prompts come from a
pool of distinct batches made the same way.
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import seeds, work as work_lib
from perfbench.harness import Check
from perfbench.reference import lm as lm_ref
from perfbench.reference import macro as macro_ref

PROJECTIONS = ("q", "k", "v", "o", "gate", "up", "down")


def model_config(config: dict):
    """The program's ModelConfig for this configuration file."""
    from repro.configs.base import CIMPolicy, ModelConfig
    from repro.core.params import CIMConfig

    hf = config["model"]
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        qkv_bias=config["qkv_bias"],
        tie_embeddings=hf["tie_word_embeddings"],
        rope_theta=float(hf["rope_theta"]), norm_eps=hf["rms_norm_eps"],
        max_seq_len=hf["max_position_embeddings"],
        activation_dtype=config["activation_dtype"],
        cim=CIMPolicy(mode=config["policy"]["mode"],
                      cim=CIMConfig(**config["macro"])),
    )


def make_params(w, cfg):
    """Seeded weights in the program's stacked layout (inside jit)."""
    L, d, hd = cfg.n_layers, cfg.d_model, cfg.head_dim
    q, kv, f = cfg.n_heads * hd, cfg.n_kv_heads * hd, cfg.d_ff
    leaves = {
        ("embed", "table"): ((cfg.padded_vocab, d), "normal", 0.02),
        ("final_norm", "scale"): ((d,), "norm", 0.05),
        ("norm1", "scale"): ((L, d), "norm", 0.05),
        ("norm2", "scale"): ((L, d), "norm", 0.05),
        ("attn", "wq", "w"): ((L, d, q), "fanin", d),
        ("attn", "wq", "b"): ((L, q), "normal", 0.02),
        ("attn", "wk", "w"): ((L, d, kv), "fanin", d),
        ("attn", "wk", "b"): ((L, kv), "normal", 0.02),
        ("attn", "wv", "w"): ((L, d, kv), "fanin", d),
        ("attn", "wv", "b"): ((L, kv), "normal", 0.02),
        ("attn", "wo", "w"): ((L, q, d), "fanin", q),
        ("mlp", "gate", "w"): ((L, d, f), "fanin", d),
        ("mlp", "up", "w"): ((L, d, f), "fanin", d),
        ("mlp", "down", "w"): ((L, f, d), "fanin", f),
    }
    root = seeds.key(w, 0)
    tree: dict = {}
    for i, (path, (shp, kind, arg)) in enumerate(leaves.items()):
        z = jax.random.normal(jax.random.fold_in(root, i), shp, jnp.float32)
        if kind == "normal":
            v = arg * z
        elif kind == "norm":
            v = 1.0 + arg * z
        else:
            v = z / np.sqrt(arg)
        if path[0] not in ("embed", "final_norm"):
            path = ("units", "layer_00") + path
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = v
    return tree


def make_prompts(w, n: int, batch: int, length: int, vocab: int):
    k = seeds.key(w, 1)
    return tuple(jax.random.randint(jax.random.fold_in(k, i),
                                    (batch, length), 0, vocab, jnp.int32)
                 for i in range(n))


class LMCell:
    def __init__(self, config, traffic, seed, devices):
        from repro.kernels import dispatch
        from repro.models import transformer
        from repro.serve.engine import ServeEngine

        self.config, self.traffic = config, traffic
        self.cfg = cfg = model_config(config)
        self.shape = work_lib.LMShape.from_config(config["model"])
        self.batch, self.prompt = traffic["batch"], traffic["prompt_len"]
        self.new = traffic["new_tokens"]
        self.units_per_call = self.batch * (
            self.new if traffic["units"] == "generated" else self.prompt)
        w = seeds.words(seed)
        self.params = jax.jit(make_params, static_argnums=1)(w, cfg)
        want = jax.eval_shape(lambda: transformer.init(
            jax.random.PRNGKey(0), cfg))
        got = jax.eval_shape(lambda: self.params)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                a.shape != b.shape for a, b in zip(
                    jax.tree.leaves(want), jax.tree.leaves(got), strict=True)):
            raise RuntimeError("seeded weights do not match the program's "
                               "parameter layout")
        self.prompts = jax.jit(make_prompts, static_argnums=(1, 2, 3, 4))(
            w, traffic["distinct_batches"], self.batch, self.prompt,
            cfg.vocab_size)
        with dispatch.record_resolutions() as log:
            self.engine = ServeEngine(
                self.params, cfg, plan=config["policy"]["plan"],
                max_len=traffic["max_len"], batch=self.batch)
            self.engine.generate(self.prompts[0], self.new)
        self.routes = self._routes(log)

    def _routes(self, log) -> dict:
        """{(phase, projection): backend} from the dispatch log; fails
        on any guard-fallback (a kernel silently replaced)."""
        bad = [r for r in log if r.source == "guard-fallback"]
        for r in log:
            print(f"  route {r.key.variant}/{r.key.backend} "
                  f"cell={r.key.shape_cell} source={r.source}"
                  f"{' block=' + str(r.block) if r.block else ''}",
                  flush=True)
        if bad:
            raise RuntimeError(f"{len(bad)} guard-fallback route(s): {bad}")
        phases = ["prefill"] + (["decode"] if self.new > 1 else [])
        if len(log) != len(PROJECTIONS) * len(phases):
            print(f"  routes: {len(log)} resolutions, expected "
                  f"{len(PROJECTIONS) * len(phases)}; not labelled",
                  flush=True)
            return {}
        routes = {}
        for i, r in enumerate(log):
            phase = phases[i // len(PROJECTIONS)]
            routes[(phase, PROJECTIONS[i % len(PROJECTIONS)])] = r.key.backend
        for phase in phases:
            print(f"  {phase} routes: " + ", ".join(
                f"{p}={routes[(phase, p)]}" for p in PROJECTIONS), flush=True)
        return routes

    def call(self, i: int) -> np.ndarray:
        p = self.prompts[i % len(self.prompts)]
        return self.engine.generate(p, self.new)  # host tokens: synced

    def work(self) -> dict:
        flops = work_lib.lm_generate_flops(self.shape, self.batch,
                                           self.prompt, self.new)
        proj = {n: (k, nn) for n, k, nn in self.shape.projections()}
        gpq = {}
        for phase, m in (("prefill", self.batch * self.prompt),
                         ("decode", self.batch)):
            shapes = [(m, *proj[p]) for p in PROJECTIONS
                      if self.routes.get((phase, p)) == "pallas"]
            gpq[phase] = shapes * self.shape.layers
        return {"flops_per_call": flops["total"], "flops": flops,
                "gpq_shapes": gpq,
                "weight_bits": self.config["macro"]["weight_bits"],
                "programs": {"prefill": "jit_prefill",
                             "decode": "jit_decode"}}

    def release(self) -> None:
        del self.engine
        gc.collect()

    def _hidden(self, i, toks, adt):
        sizes = {"layers": self.shape.layers, "head_dim": self.shape.head_dim,
                 "rope_theta": float(self.config["model"]["rope_theta"]),
                 "eps": float(self.config["model"]["rms_norm_eps"])}
        return lm_ref.final_hidden(
            self.params, self.prompts[i % len(self.prompts)],
            jnp.asarray(toks[:, :-1]), sizes,
            macro_ref.Macro.from_config(self.config["macro"]), adt,
            self.traffic["max_len"])

    def gaps(self, samples, control: bool = False) -> tuple[float, float]:
        """Widest gap by which a served token's reference logit lies
        below the reference's best; with ``control``, also the widest
        gap of the tokens that the reference computed one precision
        lower (float8 for bfloat16) puts first."""
        table = self.params["embed"]["table"]
        vocab = self.shape.vocab
        adt = jnp.dtype(self.config["activation_dtype"])
        gaps, ctl = [], []
        for i, toks in samples:
            h = self._hidden(i, toks, adt)
            gaps.append(float(jnp.max(lm_ref.token_gaps(
                h, table, vocab, jnp.asarray(toks)))))
            if control:
                pick = lm_ref.token_gaps(
                    self._hidden(i, toks, jnp.float8_e4m3fn), table, vocab)
                ctl.append(float(jnp.max(lm_ref.token_gaps(
                    h, table, vocab, pick))))
        return max(gaps), max(ctl) if ctl else float("nan")

    def check(self, samples) -> list[Check]:
        gap, _ = self.gaps(samples)
        toks = np.concatenate([t.ravel() for _, t in samples])
        out_of_vocab = float(np.sum((toks < 0) | (toks >= self.shape.vocab)))
        return [Check("logit_gap", gap, self.traffic["limits"]["logit_gap"]),
                Check("tokens_outside_vocab", out_of_vocab, 0.0)]


def setup(config, traffic, seed, devices):
    return LMCell(config, traffic, seed, devices)
