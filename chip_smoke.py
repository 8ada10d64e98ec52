"""Bring-up check: the CIM macro, ResNet-20 and served-LM paths on a TPU.

Usage (from the repository root, on a machine with a TPU):

    python chip_smoke.py            # one chip: macro parity, ResNet-20, LM
    python chip_smoke.py --chips 4  # four chips: sharded planned serving

One chip (default), in order:

1. Macro matmul parity at qwen2-0.5b projection widths: every variant
   (p8t, adder-tree, cell-adc) through every dispatch backend (scan,
   ref, slots, pallas) on planned operands, compared bit for bit with an
   integer oracle computed on the host in NumPy.
2. ResNet-20 planned inference at the paper operating point on a seeded
   batch of 256 synthetic CIFAR images; top-1 agreement with the fp
   forward of the same (seeded) weights is printed.
3. qwen2-0.5b at its published width (24 layers, d=896, vocab 151,936,
   seeded random weights) served through ``ServeEngine(plan=True)``:
   greedy tokens must equal those of ``ServeEngine(plan=False)``, the
   compiled prefill must hold a Mosaic kernel (``tpu_custom_call``), and
   a ``ContinuousBatcher`` must answer 5 mixed-length requests. The
   configuration is the published one, bfloat16 activations included.

``--chips 4`` runs only the sharded path: the planned qwen2-0.5b tree on
a (1, 4) ("data", "model") mesh, compared with a one-chip engine in the
same process (shards on 4 distinct devices, bit-identical prefill
logits, equal greedy tokens).

Every phase records its kernel routing (``dispatch.record_resolutions``)
and fails on a ``guard-fallback``. Times are one-off bring-up readings
(wall clock, and JAX's own trace/lower/compile durations), not a
benchmark. The script exits non-zero, printing no result, when JAX finds
no TPU or any check fails; otherwise the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.metadata
import json
import pathlib
import sys
import time
import traceback

import numpy as np

SEED = 0
# qwen2-0.5b projections: q/o (896x896), gate/up (896x4864) and the
# down projection (4864x896), whose K >= 4096 plans packed planes.
LM_SHAPES = ((896, 896), (896, 4864), (4864, 896))
MS = (1, 8, 128)
VARIANTS = ("p8t", "adder-tree", "cell-adc")
BACKENDS = ("scan", "ref", "slots", "pallas")
LM_BATCH, LM_PROMPT, LM_NEW, LM_MAX_LEN = 4, 16, 8, 64
# ContinuousBatcher traffic: (prompt length, new tokens) per request.
BATCHER_REQUESTS = ((3, 4), (9, 2), (5, 6), (12, 3), (7, 5))


class SmokeFailure(Exception):
    """A check of this script failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums JAX's trace, lowering and backend-compile durations."""

    EVENTS = frozenset({
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    })

    def __init__(self):
        self.total = 0.0

    def __call__(self, event: str, secs: float, **_):
        if event in self.EVENTS:
            self.total += secs


@contextlib.contextmanager
def timed(label: str, clock: CompileClock):
    t0, c0 = time.perf_counter(), clock.total
    yield
    wall = time.perf_counter() - t0
    print(f"  {label}: wall {wall:.3f} s, of which compile "
          f"{clock.total - c0:.3f} s", flush=True)


def check_routes(phase: str, log, *, show: bool = True,
                 names: dict | None = None) -> None:
    """Print the distinct resolutions; fail on any guard-fallback.

    ``names`` maps a (k, n) tuning cell to the projections it serves.
    """
    seen: dict[tuple, int] = {}
    for r in log:
        key = (r.key.variant, r.key.backend, r.key.shape_cell, r.source,
               r.block)
        seen[key] = seen.get(key, 0) + 1
    for (variant, backend, cell, source, block), count in seen.items():
        if show:
            blk = f" block={block}" if block else ""
            proj = f" [{names.get(cell[1:], '?')}]" if names else ""
            print(f"  route{proj} {variant}/{backend} cell={cell} "
                  f"source={source}{blk} x{count}", flush=True)
    fallbacks = [r for r in log if r.source == "guard-fallback"]
    check(not fallbacks, f"{phase}: {len(fallbacks)} guard-fallback "
          f"resolution(s): {fallbacks[:3]}")


# ---------------------------------------------------------------------------
# Phase 1: macro matmul parity against a host integer oracle
# ---------------------------------------------------------------------------


def oracle(x: np.ndarray, codes: np.ndarray, cfg, merged: bool) -> np.ndarray:
    """The macro matmul in integers on the host, independent of JAX.

    Per row group g and weight plane b the partial MAC is
    pmac = x[:, g] @ bit_b(w[g]) (a BLAS float64 matmul of 0..15 codes
    against 0/1 planes: exact, far below 2**53, then int64). The P-8T
    and cell-ADC transfer converts every (g, b) pMAC and shift-adds the
    codes (MSB plane negative); the adder-tree transfer merges the
    planes of a group first and converts once.
    """
    from repro.core.pipeline import as_spec
    from repro.core.variants import merged_quant

    rows, bits = cfg.rows_active, cfg.weight_bits
    m, k = x.shape
    n = codes.shape[1]
    g = -(-k // rows)
    xg = np.zeros((m, g * rows), np.float64)
    xg[:, :k] = x
    xg = xg.reshape(m, g, rows).transpose(1, 0, 2)  # [G, M, rows]
    w = np.zeros((g * rows, n), np.int64)
    w[:k] = codes
    mq = merged_quant(as_spec(cfg))
    step = mq.step if merged else cfg.adc_step
    check(float(step).is_integer(), f"oracle needs an integer ADC step: {step}")
    step = int(step)
    acc = np.zeros((g, m, n) if merged else (m, n), np.int64)
    for b in range(bits):
        plane = ((w >> b) & 1).reshape(g, rows, n).astype(np.float64)
        pmac = np.matmul(xg, plane).astype(np.int64)  # [G, M, N]
        sign = -(1 << b) if b == bits - 1 else 1 << b
        if merged:
            acc += sign * pmac
        else:
            code = np.clip(pmac // step, 0, cfg.adc_codes - 1)
            acc += sign * step * code.sum(axis=0)
    if merged:
        return np.clip(acc // step, mq.code_min, mq.code_max).sum(axis=0) * step
    return acc


def macro_parity(clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import CIMPolicy
    from repro.core import engine
    from repro.core.params import PAPER_OP_16ROWS
    from repro.kernels import dispatch

    cfg = PAPER_OP_16ROWS
    policy = CIMPolicy(mode="cim", cim=cfg)
    rng = np.random.default_rng(SEED)
    bad = []
    with dispatch.record_resolutions() as log:
        for k, n in LM_SHAPES:
            w = jnp.asarray(rng.standard_normal((k, n)) * 0.02, jnp.float32)
            plan = engine.plan_weights(w, cfg, policy, with_slots=True)
            codes = np.asarray(plan.codes).astype(np.int64)
            form = "packed" if plan.planes.ndim == 3 else "unpacked"
            print(f"  plan {k}x{n}: codes {plan.codes.dtype}, {form} planes "
                  f"{plan.planes.shape}, slots {plan.slots.shape}", flush=True)
            for m in MS:
                x = rng.integers(0, cfg.act_levels, (m, k)).astype(np.int32)
                want = {
                    "floor": oracle(x, codes, cfg, merged=False),
                    "merged": oracle(x, codes, cfg, merged=True),
                }
                for variant in VARIANTS:
                    ref = want["merged" if variant == "adder-tree" else "floor"]
                    for backend in BACKENDS:
                        fn = jax.jit(
                            lambda xx, ww, pp, ss, _v=variant, _b=backend:
                            dispatch.dispatch(
                                xx, ww, cfg, variant=_v, backend=_b,
                                planes=pp, slots=ss,
                            )
                        )
                        args = (jnp.asarray(x), plan.codes, plan.planes,
                                plan.slots)
                        c0 = clock.total
                        t0 = time.perf_counter()
                        got = np.asarray(jax.block_until_ready(fn(*args)))
                        first = time.perf_counter() - t0
                        t0 = time.perf_counter()
                        jax.block_until_ready(fn(*args))
                        run = time.perf_counter() - t0
                        diff = np.abs(got.astype(np.float64) - ref)
                        n_bad = int(np.count_nonzero(diff))
                        verdict = "exact" if n_bad == 0 else (
                            f"MISMATCH {n_bad}/{diff.size} "
                            f"max|err|={diff.max():.6g}"
                        )
                        print(f"  {variant}/{backend} {k}x{n} m={m}: "
                              f"{verdict} (first call {first:.3f} s, "
                              f"compile {clock.total - c0:.3f} s, "
                              f"run {run * 1e3:.3f} ms)", flush=True)
                        if n_bad:
                            bad.append((variant, backend, k, n, m, n_bad))
    wrong = [r for r in log if r.key.backend not in BACKENDS
             or r.source != "explicit"]
    check_routes("macro", log, show=False)
    check(not wrong, f"macro: unexpected resolutions {wrong[:3]}")
    check(not bad, f"macro: {len(bad)} result(s) differ from the integer "
          f"oracle: {bad}")


# ---------------------------------------------------------------------------
# Phase 2: ResNet-20 at the paper operating point
# ---------------------------------------------------------------------------


def resnet_phase(clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import resnet20_cifar
    from repro.configs.base import CIMPolicy
    from repro.data.synthetic import SyntheticCIFAR
    from repro.kernels import dispatch
    from repro.models import resnet

    cfg = resnet20_cifar.CONFIG
    fp_cfg = dataclasses.replace(
        cfg, cim=CIMPolicy(mode="fp", act_symmetric=True)
    )
    print(f"  operating point {cfg.cim.cim}", flush=True)
    params, bn = resnet.init(jax.random.PRNGKey(SEED), cfg)
    planned = resnet.plan_params(params, cfg.cim)
    images = jnp.asarray(SyntheticCIFAR(n_classes=cfg.n_classes, seed=SEED)
                         .batch(256, step=0, train=False)["image"])
    fwd = jax.jit(lambda p, b, x: resnet.forward(p, b, x, cfg)[0])
    fp_fwd = jax.jit(lambda p, b, x: resnet.forward(p, b, x, fp_cfg)[0])
    with dispatch.record_resolutions() as log:
        with timed("planned CIM forward, first call", clock):
            logits = np.asarray(jax.block_until_ready(fwd(planned, bn, images)))
    with timed("planned CIM forward, second call", clock):
        jax.block_until_ready(fwd(planned, bn, images))
    with timed("fp forward, first call", clock):
        fp_logits = np.asarray(jax.block_until_ready(fp_fwd(params, bn, images)))
    check_routes("resnet", log)
    check(logits.shape == (256, cfg.n_classes), f"logits {logits.shape}")
    check(bool(np.isfinite(logits).all()), "non-finite CIM logits")
    agree = float(np.mean(logits.argmax(-1) == fp_logits.argmax(-1)))
    # Seeded random weights: say how many classes the fp forward even
    # predicts, since agreement on one class would say little.
    n_cls = len(np.unique(fp_logits.argmax(-1)))
    print(f"  top-1 agreement with the fp forward: {agree:.4f} "
          f"(fp predicts {n_cls} distinct classes; {len(log)} macro "
          "matmuls traced)", flush=True)


# ---------------------------------------------------------------------------
# Phase 3: served qwen2-0.5b at full width
# ---------------------------------------------------------------------------


def lm_setup():
    import jax

    from repro.configs.base import CIMPolicy, get_config
    from repro.core.params import PAPER_OP_16ROWS
    from repro.models import transformer

    cfg = get_config("qwen2_0_5b").replace(
        cim=CIMPolicy(mode="cim", cim=PAPER_OP_16ROWS),
    )
    print(f"  {cfg.name}: {cfg.n_layers} layers, d={cfg.d_model}, "
          f"d_ff={cfg.d_ff}, vocab={cfg.vocab_size}, activations "
          f"{cfg.activation_dtype}", flush=True)
    params = transformer.init(jax.random.PRNGKey(SEED), cfg)
    prompts = jax.random.randint(
        jax.random.PRNGKey(SEED + 1), (LM_BATCH, LM_PROMPT), 0,
        cfg.vocab_size,
    )
    return cfg, params, prompts


def projection_names(cfg, shards: int = 1) -> dict:
    """(k, n) tuning cell -> the LM projections that land in it.

    ``shards``: the model-axis size of a sharded engine, whose devices
    each see 1/shards of an evenly divisible N.
    """
    from repro.kernels.dispatch import shape_cell

    names: dict = {}
    for proj, (k, n) in (
        ("q", (cfg.d_model, cfg.q_dim)),
        ("k/v", (cfg.d_model, cfg.kv_dim)),
        ("o", (cfg.q_dim, cfg.d_model)),
        ("gate/up", (cfg.d_model, cfg.d_ff)),
        ("down", (cfg.d_ff, cfg.d_model)),
    ):
        if n % shards == 0:
            n //= shards
        cell = shape_cell(1, k, n)[1:]
        names[cell] = f"{names[cell]}/{proj}" if cell in names else proj
    return names


def generate_traced(name, engine, prompts, clock, *, repeat: bool = True,
                    shards: int = 1):
    """First (tracing) generate under record_resolutions; with
    ``repeat`` a second call must give the same tokens."""
    from repro.kernels import dispatch

    with dispatch.record_resolutions() as log:
        with timed(f"{name} generate, first call", clock):
            toks = engine.generate(prompts, LM_NEW)
    if repeat:
        with timed(f"{name} generate, second call", clock):
            again = engine.generate(prompts, LM_NEW)
        check(np.array_equal(toks, again),
              f"{name}: greedy tokens not repeatable")
    check_routes(name, log, names=projection_names(engine.cfg, shards))
    return toks


def lm_phase(clock: CompileClock) -> None:
    from repro.serve.engine import ContinuousBatcher, Request, ServeEngine

    cfg, params, prompts = lm_setup()
    kw = dict(max_len=LM_MAX_LEN, batch=LM_BATCH)
    with timed("plan + place (plan=True)", clock):
        planned = ServeEngine(params, cfg, plan=True, **kw)
    toks_p = generate_traced("planned", planned, prompts, clock)
    with timed("prefill HLO check", clock):
        hlo = planned._prefill.lower(
            planned.params, prompts, planned.caches
        ).compile().as_text()
    check("tpu_custom_call" in hlo,
          "compiled prefill holds no tpu_custom_call (no Mosaic kernel)")
    print(f"  compiled prefill: {hlo.count('tpu_custom_call')} "
          "tpu_custom_call site(s)", flush=True)

    unplanned = ServeEngine(params, cfg, plan=False, **kw)
    toks_u = generate_traced("unplanned", unplanned, prompts, clock)
    print(f"  planned tokens[0]:   {toks_p[0].tolist()}", flush=True)
    print(f"  unplanned tokens[0]: {toks_u[0].tolist()}", flush=True)
    check(np.array_equal(toks_p, toks_u),
          f"planned and unplanned greedy tokens differ at "
          f"{int(np.count_nonzero(toks_p != toks_u))} of {toks_p.size}")

    batcher = ContinuousBatcher(planned, eos_token=-1)
    rng = np.random.default_rng(SEED)
    for rid, (plen, new) in enumerate(BATCHER_REQUESTS):
        batcher.submit(Request(
            rid=rid, prompt=rng.integers(0, cfg.vocab_size, plen),
            max_new=new,
        ))
    with timed(f"ContinuousBatcher, {len(BATCHER_REQUESTS)} requests", clock):
        done = batcher.run_until_done(max_ticks=500)
    check(len(done) == len(BATCHER_REQUESTS),
          f"batcher answered {len(done)} of {len(BATCHER_REQUESTS)}")
    want = dict(enumerate(n for _, n in BATCHER_REQUESTS))
    check(all(len(r.generated) == want[r.rid] for r in done),
          "batcher: a request got the wrong number of tokens")
    check(all(0 <= t < cfg.vocab_size for r in done for t in r.generated),
          "batcher: token outside the vocabulary")
    print(f"  batcher answered {len(done)} requests "
          f"(rids in completion order {[r.rid for r in done]})", flush=True)


# ---------------------------------------------------------------------------
# Four chips: sharded planned serving vs one chip
# ---------------------------------------------------------------------------


def sharded_phase(clock: CompileClock) -> None:
    import jax

    from repro.core.engine import PlannedWeights
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer
    from repro.serve.engine import ServeEngine

    check(len(jax.devices()) >= 4, f"need 4 devices, have {jax.devices()}")
    mesh = make_host_mesh((1, 4))
    cfg, params, prompts = lm_setup()
    kw = dict(max_len=LM_MAX_LEN, batch=LM_BATCH, plan=True)
    with timed("plan + shard over (1, 4)", clock):
        sharded = ServeEngine(params, cfg, mesh=mesh, **kw)
    one = ServeEngine(params, cfg, **kw)
    plans = [p for p in jax.tree.leaves(
        sharded.params, is_leaf=lambda v: isinstance(v, PlannedWeights))
        if isinstance(p, PlannedWeights)]
    check(bool(plans), "no planned leaves in the sharded tree")
    for p in plans:
        devs = {s.device for s in p.codes.addressable_shards}
        check(len(devs) == 4, f"codes {p.codes.shape} on {len(devs)} devices")
        n = p.codes.shape[-1]
        local = p.codes.addressable_shards[0].data.shape[-1]
        check(n % 4 != 0 or local == n // 4,
              f"codes {p.codes.shape}: shard holds {local} of {n} columns")
    print(f"  {len(plans)} planned leaves, each on 4 distinct devices "
          f"(codes split over 'model')", flush=True)
    # One generate each: a repeat would only recompile for the caches'
    # new shardings, at four chips' cost.
    toks_s = generate_traced("sharded", sharded, prompts, clock,
                             repeat=False, shards=4)
    toks_1 = generate_traced("one-chip", one, prompts, clock, repeat=False)
    print(f"  sharded tokens[0]:  {toks_s[0].tolist()}", flush=True)
    print(f"  one-chip tokens[0]: {toks_1[0].tolist()}", flush=True)
    caches = transformer.init_caches(cfg, LM_BATCH, LM_MAX_LEN,
                                     dtype=cfg.activation_dtype)
    logits = [np.asarray(e._prefill(e.params, prompts, caches)[0])
              for e in (sharded, one)]
    n_diff = int(np.count_nonzero(logits[0] != logits[1]))
    print(f"  prefill logits differing from one chip: {n_diff} of "
          f"{logits[0].size}", flush=True)
    check(n_diff == 0, "sharded prefill logits differ from one chip's")
    check(np.array_equal(toks_s, toks_1),
          f"sharded and one-chip greedy tokens differ at "
          f"{int(np.count_nonzero(toks_s != toks_1))} of {toks_s.size}")


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded serving path")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"error: no TPU (JAX found {dev.platform}); this check runs "
              "on the chip only", file=sys.stderr)
        return 1

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    import jaxlib

    print(f"device: {dev.device_kind} x{len(devices)} "
          f"(platform {dev.platform})", flush=True)
    print(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
          f"libtpu {libtpu}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    if args.chips == 4:
        phases = [("sharded serving, 4 chips vs 1", sharded_phase)]
    else:
        phases = [
            ("macro parity", macro_parity),
            ("resnet20 planned inference", resnet_phase),
            ("qwen2-0.5b served", lm_phase),
        ]
    failed = []
    for name, fn in phases:
        print(f"[{name}]", flush=True)
        t0, c0 = time.perf_counter(), clock.total
        try:
            fn(clock)
        except Exception as e:  # noqa: BLE001 - report, run the next phase
            traceback.print_exc()
            failed.append(name)
            print(f"[{name}] FAILED: {type(e).__name__}: {e}", flush=True)
        print(f"[{name}] wall {time.perf_counter() - t0:.3f} s, compile "
              f"{clock.total - c0:.3f} s", flush=True)
    if failed:
        print(f"FAILED phases: {failed}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
