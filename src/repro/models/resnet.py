"""ResNet-20 (CIFAR) -- the paper's own evaluation network.

Convolutions execute as im2col + the core.engine CIM matmul so the
whole network can run through the macro model exactly as the paper's
system simulations do (4-bit unsigned post-ReLU activations, 8-bit
weights, grouped ADC readout with cutoff quantization, optional
hardware errors).

Weight-stationary evaluation: ``plan_params(params, policy)`` converts
every conv/fc weight into its im2col matrix's ``engine.PlannedWeights``
once, so repeated-inference sweeps (Table I / Fig. 7 accuracy studies,
serving) stop re-quantizing and re-bit-slicing weights on every
forward — mirroring the macro, whose SRAM weights are written once.

Functional with explicit BatchNorm state:
  forward(params, bn_state, x, cfg, train) -> (logits, new_bn_state)

Device scopes (``jax.named_scope``, metadata only): ``resnet.im2col``,
``resnet.bn``, ``resnet.pool``; the macro convs carry the engine's
``cim.*`` scopes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import CIMPolicy
from repro.core import engine
from repro.core.engine import PlannedWeights
from repro.models import common
from repro.models.common import ParamSpec


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("plan",),
    meta_fields=("kernel_hw",),
)
@dataclasses.dataclass(frozen=True)
class PlannedConv:
    """A conv filter's weight-stationary plan + its spatial geometry.

    The im2col plan alone cannot recover (kh, kw) — pf = kh*kw*cin is
    ambiguous — so the filter window rides along as static metadata.
    """

    plan: PlannedWeights
    kernel_hw: tuple[int, int]


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    n_classes: int = 10
    widths: tuple[int, ...] = (16, 32, 64)
    blocks_per_stage: int = 3  # ResNet-20 = 1 + 2*3*3 + 1 layers
    bn_momentum: float = 0.9
    cim: CIMPolicy = dataclasses.field(
        default_factory=lambda: CIMPolicy(mode="fp", act_symmetric=True)
    )


def _conv_spec(kh, kw, cin, cout):
    return ParamSpec((kh, kw, cin, cout), (None, None, "embed", "mlp"),
                     "fanin")


def _bn_spec(c):
    return {
        "scale": ParamSpec((c,), (None,), "ones"),
        "bias": ParamSpec((c,), (None,), "zeros"),
    }


def _block_spec(cin, cout):
    spec = {
        "conv1": _conv_spec(3, 3, cin, cout),
        "bn1": _bn_spec(cout),
        "conv2": _conv_spec(3, 3, cout, cout),
        "bn2": _bn_spec(cout),
    }
    if cin != cout:
        spec["proj"] = _conv_spec(1, 1, cin, cout)
        spec["bn_proj"] = _bn_spec(cout)
    return spec


def model_spec(cfg: ResNetConfig) -> dict:
    w = cfg.widths
    spec: dict = {"stem": _conv_spec(3, 3, 3, w[0]), "bn_stem": _bn_spec(w[0])}
    cin = w[0]
    for si, cout in enumerate(w):
        for bi in range(cfg.blocks_per_stage):
            spec[f"s{si}b{bi}"] = _block_spec(cin, cout)
            cin = cout
    spec["fc"] = common.linear_spec(w[-1], cfg.n_classes, "embed", "vocab",
                                    bias=True)
    return spec


def init(key: jax.Array, cfg: ResNetConfig):
    params = common.init_params(key, model_spec(cfg))
    bn_state = _init_bn_state(params)
    return params, bn_state


def _init_bn_state(params, prefix=()):
    state = {}
    for k, v in params.items():
        if k.startswith("bn"):
            c = v["scale"].shape[0]
            state[k] = {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}
        elif isinstance(v, dict) and not {"w", "b"} >= set(v.keys()):
            sub = _init_bn_state(v)
            if sub:
                state[k] = sub
    return state


def _im2col_weight(params_w: jax.Array) -> jax.Array:
    """[kh, kw, cin, cout] -> the [cin*kh*kw, cout] im2col matrix.

    conv_general_dilated_patches orders patch features as [cin, kh, kw];
    the weight matrix is reordered to match.
    """
    kh, kw, cin, cout = params_w.shape
    return jnp.transpose(params_w, (2, 0, 1, 3)).reshape(
        kh * kw * cin, cout
    )


def _conv(params_w, x, stride, policy: CIMPolicy | None,
          key=None, cim_enabled: bool = True, *, name: str = "",
          tap=None):
    """Conv as im2col + (CIM) matmul. x: [B, H, W, C] NHWC.

    params_w is either the raw [kh, kw, cin, cout] filter or a
    PlannedConv over its im2col matrix (see plan_params).

    ``tap(name, x2, w)`` observes the im2col activations [M, K] and the
    weight (im2col matrix or PlannedWeights) of every macro-eligible
    conv — the capture hook core.calibrate uses for the hardware-aware
    per-layer sweep. Taps run eagerly (they see concrete arrays), so
    pass them only to un-jitted forwards; a tapped fp forward takes the
    im2col path (float association differs from lax.conv at ~1e-7).
    """
    planned = isinstance(params_w, PlannedConv)
    want_tap = tap is not None and cim_enabled
    if planned:
        kernel_hw = params_w.kernel_hw
    else:
        kernel_hw = params_w.shape[:2]
        if (policy is None or policy.mode == "fp" or not cim_enabled) \
                and not want_tap:
            return jax.lax.conv_general_dilated(
                x, params_w, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
    with jax.named_scope("resnet.im2col"):
        patches = jax.lax.conv_general_dilated_patches(
            x, tuple(kernel_hw), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )  # [B, Ho, Wo, cin*kh*kw] (channel-major patch layout)
        b, ho, wo, pf = patches.shape
        x2 = patches.reshape(-1, pf)
    if planned:
        plan = params_w.plan
        assert plan.k == pf, (plan.k, pf, kernel_hw)
        cout = plan.n
        if want_tap:
            tap(name, x2, plan)
        if policy is None or policy.mode == "fp" or not cim_enabled:
            y = x2 @ plan.best_weights(x2.dtype)
        else:
            y = engine.execute(x2, plan, policy, key=key)
    else:
        wmat = _im2col_weight(params_w)
        cout = wmat.shape[-1]
        if want_tap:
            tap(name, x2, wmat)
        y = engine.matmul(x2, wmat, policy, key=key)
    return y.reshape(b, ho, wo, cout)


def plan_params(params: dict, policy: CIMPolicy) -> dict:
    """Precompute weight-stationary plans for every conv/fc weight.

    Conv filters are planned as their im2col matrices (the layout the
    macro sees); the fc layer's 'w' leaf is planned by engine.plan_params
    semantics. BatchNorm / bias leaves pass through untouched, and an
    exempt stem (policy.apply_to_stem=False) keeps its raw filter so
    the digital lax.conv path stays bit-identical. Plans keep the float
    weights, so digitally-exempt layers (logits by default) are exact.
    """

    def walk(node):
        out = {}
        for k, v in node.items():
            if isinstance(v, dict):
                out[k] = walk(v)
            elif k == "stem" and not policy.apply_to_stem:
                out[k] = v  # digital conv: keep the [kh,kw,cin,cout] form
            elif k.startswith(("conv", "stem", "proj")) and v.ndim == 4:
                out[k] = PlannedConv(
                    plan=engine.plan_weights(
                        _im2col_weight(v), policy.cim, policy,
                        keep_fp=True,
                    ),
                    kernel_hw=tuple(v.shape[:2]),
                )
            elif k == "w" and v.ndim == 2:
                out[k] = engine.plan_weights(
                    v, policy.cim, policy, keep_fp=True
                )
            else:
                out[k] = v
        return out

    return walk(params)


@jax.named_scope("resnet.bn")
def _bn(params, state, x, train: bool, momentum: float):
    if train:
        mu = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.var(x, axis=(0, 1, 2))
        new_state = {
            "mean": momentum * state["mean"] + (1 - momentum) * mu,
            "var": momentum * state["var"] + (1 - momentum) * var,
        }
    else:
        mu, var = state["mean"], state["var"]
        new_state = state
    y = (x - mu) * jax.lax.rsqrt(var + 1e-5)
    return y * params["scale"] + params["bias"], new_state


def forward(
    params: dict,
    bn_state: dict,
    x: jax.Array,  # [B, 32, 32, 3]
    cfg: ResNetConfig,
    *,
    train: bool = False,
    key: jax.Array | None = None,
    tap=None,
) -> tuple[jax.Array, dict]:
    policy = cfg.cim
    new_state: dict[str, Any] = {}
    kidx = [0]

    def nk():
        kidx[0] += 1
        return None if key is None else jax.random.fold_in(key, kidx[0])

    h = _conv(params["stem"], x, 1, policy, key=nk(),
              cim_enabled=policy.apply_to_stem, name="stem", tap=tap)
    h, new_state["bn_stem"] = _bn(params["bn_stem"], bn_state["bn_stem"],
                                  h, train, cfg.bn_momentum)
    h = jax.nn.relu(h)

    cin = cfg.widths[0]
    for si, cout in enumerate(cfg.widths):
        for bi in range(cfg.blocks_per_stage):
            name = f"s{si}b{bi}"
            bp, bs = params[name], bn_state[name]
            ns = {}
            stride = 2 if (bi == 0 and si > 0) else 1
            r = _conv(bp["conv1"], h, stride, policy, key=nk(),
                      name=f"{name}/conv1", tap=tap)
            r, ns["bn1"] = _bn(bp["bn1"], bs["bn1"], r, train,
                               cfg.bn_momentum)
            r = jax.nn.relu(r)
            r = _conv(bp["conv2"], r, 1, policy, key=nk(),
                      name=f"{name}/conv2", tap=tap)
            r, ns["bn2"] = _bn(bp["bn2"], bs["bn2"], r, train,
                               cfg.bn_momentum)
            if "proj" in bp:
                sc = _conv(bp["proj"], h, stride, policy, key=nk(),
                           name=f"{name}/proj", tap=tap)
                sc, ns["bn_proj"] = _bn(bp["bn_proj"], bs["bn_proj"], sc,
                                        train, cfg.bn_momentum)
            else:
                sc = h
            h = jax.nn.relu(r + sc)
            new_state[name] = ns
            cin = cout

    with jax.named_scope("resnet.pool"):
        h = jnp.mean(h, axis=(1, 2))  # global average pool
    logits = common.linear_apply(params["fc"], h, policy,
                                 cim_enabled=policy.apply_to_logits,
                                 key=nk())
    return logits, new_state


def top1_accuracy(
    params: dict,
    bn_state: dict,
    images: jax.Array,
    labels: jax.Array,
    cfg: ResNetConfig,
    *,
    key: jax.Array | None = None,
    batch_size: int | None = None,
) -> float:
    """Held-out top-1 accuracy of (possibly planned) params.

    The end-to-end objective the accuracy-refinement phase of
    ``core.calibrate.refine`` optimizes: every conv runs its real
    execution path (im2col -> ``engine.execute`` -> kernels.dispatch)
    under ``cfg.cim``, so a calibrated/refined backend is measured
    exactly as it will serve. Eager (no jit): candidate operating
    points change per call, and held-out batches are small.
    """
    labels = jnp.asarray(labels)
    n = int(images.shape[0])
    bs = n if batch_size is None else int(batch_size)
    correct = 0
    for s in range(0, n, bs):
        k = None if key is None else jax.random.fold_in(key, s)
        logits, _ = forward(params, bn_state, images[s:s + bs], cfg,
                            train=False, key=k)
        pred = jnp.argmax(logits, axis=-1)
        correct += int(jnp.sum(pred == labels[s:s + bs]))
    return correct / n


def loss_fn(params, bn_state, batch, cfg: ResNetConfig, *, train=True,
            key=None):
    logits, new_state = forward(params, bn_state, batch["image"], cfg,
                                train=train, key=key)
    labels = batch["label"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, (new_state, {"loss": loss, "acc": acc})
