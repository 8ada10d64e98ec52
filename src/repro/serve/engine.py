"""Serving engine: prefill + greedy decode with continuous batching.

ServeEngine drives the transformer serving path (init_caches ->
prefill -> decode_step) with jitted steps. The slot-based continuous
batcher admits new requests into finished slots between decode steps --
the scheduling pattern real LM servers use, scaled down to one process.
Decode caches are donated so the cache update is in-place on device.

Weight-stationary serving: ``ServeEngine(..., plan=True)`` runs
``core.engine.plan_params`` over the model parameters once at
construction, so every prefill/decode step reuses precomputed weight
codes/colsums/scales instead of re-quantizing the weight side per
matmul -- the serving analogue of the paper's SRAM-resident weights.
Under a CIM-mode policy the planned codes equal the per-call ones, so
token streams are bit-identical to the unplanned engine (tested, and
on a TPU at bfloat16 activations by ``chip_smoke.py``; the steps
compile with every declared dtype honoured, see ``_EXACT_DTYPES``);
under an 'fp' policy planning instead means digital int8 weight-only
serving (plans drop the float weights for the HBM-traffic win).

Planned trees persist through ``checkpoint.store`` (PlannedWeights is a
registered dataclass, so its leaves checkpoint under attribute paths):
``ServeEngine.restore_planned`` warm-starts a server from such a
checkpoint without re-quantizing / re-bit-slicing any weight.

Plan-aware scaling:

* **Donated plan buffers** (``donate_plan=True``, opt-in) — the jitted
  decode step takes the params as a donated argument and returns them
  unchanged, so XLA aliases the plan buffers input->output and may
  reuse their memory across the step. Donation deletes the caller's
  input arrays, so the engine first takes a one-time private copy of
  the tree — a deliberate trade (transient 2x at construction; the
  caller's tree stays valid) that only pays off on backends/steps
  where XLA exploits the aliasing; leave it off (the default) on
  memory-bound single-host CPU serving, where non-donated jit inputs
  are already zero-copy.
* **Sharded planes** — ``mesh=`` places the planned tree under
  ``distributed.sharding.shard_planned`` (codes, kept fp weights and
  packed/unpacked ``planes`` split over the model axis on their
  output-channel dim) and runs each step under
  ``distributed.sharding.per_device``: each device computes its own
  output columns of every macro matmul and all-gathers the integer
  result; every other read of a plan (fp matmuls, the MoE expert banks,
  mamba's direct projections) gathers the weights. Everything else
  (epilogue, norms, attention, caches) runs on whole tensors, repeated
  on each device: the one-device program plus the gathers, so the
  token stream equals the one-device engine's (tested). Mesh axes other
  than 'model' split nothing.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import engine as cim_engine
from repro.models import transformer

# Serving steps compile with every declared dtype honoured. Allowed
# excess precision, XLA keeps some bfloat16 intermediates in float32,
# where its fusion decisions happen to let it; those differ between two
# programs (planned vs unplanned, sharded vs one device), and a 4-bit
# activation quantizer turns one rounding more or less into another
# code. On a TPU v5e that parted planned and unplanned qwen2-0.5b token
# streams at bfloat16 activations.
_EXACT_DTYPES = {"xla_allow_excess_precision": False}


class ServeEngine:
    def __init__(self, params, cfg: ModelConfig, *, max_len: int,
                 batch: int, plan: bool = False, donate_plan: bool = False,
                 mesh=None, calibration=None):
        if calibration is not None and cfg.cim.backend and \
                not cim_engine.is_builtin_backend(cfg.cim.backend):
            # Serving a (restored) calibration: the explicitly passed
            # result wins — register it under the policy's backend
            # name, overwriting any calibration previously registered
            # there, so a stale backend can never silently serve
            # another result's specs (e.g. `load_result(path)` in a
            # process that already served a different calibration).
            # Built-in backends are never clobbered; against those the
            # calibration is plan-grouping-only.
            calibration.register(cfg.cim.backend)
        if plan:
            params = cim_engine.plan_params(
                params, policy=cfg.cim, calibration=calibration
            )
        if donate_plan:
            # Donation hands the param buffers to XLA every step, which
            # deletes the input arrays; callers routinely share one
            # params tree across engines (or keep using it), so the
            # engine takes a one-time private copy it then owns
            # exclusively (see the module docstring for the trade).
            params = jax.tree.map(
                lambda x: jnp.array(x, copy=True), params
            )
        if mesh is not None:
            from repro.distributed import sharding  # lazy: optional at serve

            params = sharding.shard_planned(params, mesh)
        self.params = params
        self.cfg = cfg
        self.max_len = max_len
        self.batch = batch
        self.mesh = mesh
        self._donate_plan = donate_plan
        self.caches = transformer.init_caches(
            cfg, batch, max_len,
            dtype=jnp.dtype(cfg.activation_dtype),
        )

        def prefill(p, t, c):
            return transformer.prefill(p, t, c, cfg)

        def decode(p, tok, pos, c):
            return transformer.decode_step(p, tok, pos, c, cfg)

        if mesh is not None:
            prefill = sharding.per_device(prefill, params, mesh)
            decode = sharding.per_device(decode, params, mesh)
        self._prefill = jax.jit(prefill, compiler_options=_EXACT_DTYPES)
        if donate_plan:
            # The decode step returns the (unchanged) params so XLA
            # aliases the donated plan buffers input->output; the
            # caches stay donated as before. self.params MUST be
            # rebound from the step's third output (_decode_step).
            self._decode = jax.jit(
                lambda p, tok, pos, c: decode(p, tok, pos, c) + (p,),
                donate_argnums=(0, 3), compiler_options=_EXACT_DTYPES,
            )
        else:
            self._decode = jax.jit(decode, donate_argnums=(3,),
                                   compiler_options=_EXACT_DTYPES)

    def _decode_step(self, tok, pos):
        """One decode step, rebinding the donated plan buffers."""
        out = self._decode(self.params, tok, pos, self.caches)
        if self._donate_plan:
            logits, self.caches, self.params = out
        else:
            logits, self.caches = out
        return logits

    @classmethod
    def restore_planned(
        cls,
        directory,
        cfg: ModelConfig,
        *,
        max_len: int,
        batch: int,
        step: int | None = None,
        calibration=None,
    ) -> "ServeEngine":
        """Warm-start a server from a checkpointed *planned* tree.

        The restore target is built structurally (``jax.eval_shape``
        over init + ``plan_params`` over the ShapeDtypeStruct tree), so
        no weight is materialized, quantized or bit-sliced here — the
        plans come back exactly as the saver wrote them. Counterpart of
        ``store.save(plan_params(params, policy=cfg.cim), dir, step)``
        (or ``Trainer.planned_params`` at the train->serve handoff).

        ``calibration`` must match the saver's: it shapes the restore
        target (plans grouped at each layer's calibrated ``rows_active``)
        and is registered as ``cfg.cim.backend`` if that backend is not
        live yet — so a refined result persisted with
        ``calibrate.save_result`` restores and serves in one call.
        """
        from repro.checkpoint import store  # lazy: optional at serve time

        sds_params = jax.eval_shape(
            lambda: transformer.init(jax.random.PRNGKey(0), cfg)
        )
        target = cim_engine.plan_params(
            sds_params, policy=cfg.cim, calibration=calibration
        )
        planned = store.restore(directory, target, step=step)
        return cls(planned, cfg, max_len=max_len, batch=batch, plan=False,
                   calibration=calibration)

    def generate(self, prompts: jax.Array, n_tokens: int) -> np.ndarray:
        """Greedy-decode n_tokens after the prompt batch [B, S]."""
        b, s = prompts.shape
        assert b == self.batch
        logits, self.caches = self._prefill(self.params, prompts,
                                            self.caches)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = [tok]
        for i in range(n_tokens - 1):
            pos = jnp.asarray(s + i, dtype=jnp.int32)
            logits = self._decode_step(tok, pos)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(tok)
        return np.stack([np.asarray(t) for t in out], axis=1)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [S]
    max_new: int
    generated: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ContinuousBatcher:
    """Slot-based continuous batching over a fixed decode batch.

    Each slot holds one in-flight request; finished slots are refilled
    from the queue between decode steps. Per-slot positions let
    requests of different lengths share one decode step (the cache is
    written at each slot's own position).

    Implementation note: per-slot positions require a vectorized decode
    (position vector instead of scalar); we run one decode_step per
    unique position group -- adequate for the example scale, and the
    scheduling logic (admission, eviction, fairness) is the part that
    carries to a real deployment.
    """

    def __init__(self, engine: ServeEngine, eos_token: int = 0):
        self.engine = engine
        self.eos = eos_token
        self.slots: list[Request | None] = [None] * engine.batch
        self.queue: list[Request] = []
        self.completed: list[Request] = []
        self._positions = np.zeros(engine.batch, dtype=np.int64)

    def submit(self, req: Request):
        self.queue.append(req)

    def _admit(self):
        for i, slot in enumerate(self.slots):
            if slot is None and self.queue:
                req = self.queue.pop(0)
                self.slots[i] = req
                # Prefill this slot: run the prompt through decode steps
                # (single-slot prefill keeps the example simple).
                for t, tok in enumerate(req.prompt):
                    self._step_slot(i, int(tok), t)
                self._positions[i] = len(req.prompt)

    def _step_slot(self, slot: int, token: int, pos: int) -> int:
        b = self.engine.batch
        toks = np.zeros((b,), dtype=np.int32)
        toks[slot] = token
        logits = self.engine._decode_step(
            jnp.asarray(toks), jnp.asarray(pos, dtype=jnp.int32)
        )
        return int(np.asarray(jnp.argmax(logits[slot])))

    def step(self):
        """One scheduler tick: admit, decode each active slot, retire."""
        self._admit()
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            last = (
                req.generated[-1]
                if req.generated
                else int(req.prompt[-1])
            )
            nxt = self._step_slot(i, last, int(self._positions[i]))
            req.generated.append(nxt)
            self._positions[i] += 1
            if len(req.generated) >= req.max_new or nxt == self.eos:
                req.done = True
                self.completed.append(req)
                self.slots[i] = None

    def run_until_done(self, max_ticks: int = 10_000):
        ticks = 0
        while (self.queue or any(self.slots)) and ticks < max_ticks:
            self.step()
            ticks += 1
        return self.completed
