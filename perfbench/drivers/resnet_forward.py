"""A CIFAR ResNet served as back-to-back jitted ``resnet.forward`` calls
on ``resnet.plan_params`` output.

The configuration file gives the network and the macro's operating
point; the traffic gives the batch and how many distinct batches the
device holds. Weights, BatchNorm statistics and CIFAR-shaped images
are made by this driver from the seed, on the device.
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import seeds, work as work_lib
from perfbench.harness import Check
from perfbench.reference import macro as macro_ref
from perfbench.reference import resnet as resnet_ref


def resnet_config(config: dict):
    from repro.configs.base import CIMPolicy
    from repro.core.params import CIMConfig
    from repro.models.resnet import ResNetConfig

    if config["shortcut"] != "projection":
        raise ValueError("the program's ResNet has 1x1 projection "
                         f"shortcuts only, not {config['shortcut']!r}")
    pol = config["policy"]
    return ResNetConfig(
        n_classes=config["n_classes"], widths=tuple(config["widths"]),
        blocks_per_stage=config["blocks_per_stage"],
        cim=CIMPolicy(mode=pol["mode"], cim=CIMConfig(**config["macro"]),
                      act_symmetric=pol["act_symmetric"],
                      apply_to_logits=pol["apply_to_logits"],
                      apply_to_stem=pol["apply_to_stem"]),
    )


def make_params(w, widths: tuple, blocks: int, n_classes: int):
    """Seeded weights and BatchNorm statistics in the program's layout."""
    root = seeds.key(w, 0)
    count = [0]

    def normal(shape, scale=1.0, shift=0.0):
        count[0] += 1
        z = jax.random.normal(jax.random.fold_in(root, count[0]), shape)
        return shift + scale * z

    def conv(kh, kw, cin, cout):
        return normal((kh, kw, cin, cout), (kh * kw * cin) ** -0.5)

    def bn(c):
        return ({"scale": normal((c,), 0.1, 1.0), "bias": normal((c,), 0.1)},
                {"mean": normal((c,), 0.1),
                 "var": 1.0 + 0.2 * jnp.abs(normal((c,)))})

    params, state = {"stem": conv(3, 3, 3, widths[0])}, {}
    params["bn_stem"], state["bn_stem"] = bn(widths[0])
    cin = widths[0]
    for si, cout in enumerate(widths):
        for bi in range(blocks):
            p, s = {}, {}
            p["conv1"] = conv(3, 3, cin, cout)
            p["bn1"], s["bn1"] = bn(cout)
            p["conv2"] = conv(3, 3, cout, cout)
            p["bn2"], s["bn2"] = bn(cout)
            if cin != cout:
                p["proj"] = conv(1, 1, cin, cout)
                p["bn_proj"], s["bn_proj"] = bn(cout)
            params[f"s{si}b{bi}"], state[f"s{si}b{bi}"] = p, s
            cin = cout
    params["fc"] = {"w": normal((cin, n_classes), cin ** -0.5),
                    "b": normal((n_classes,), 0.1)}
    return params, state


def make_images(w, n: int, batch: int, n_classes: int):
    """CIFAR-shaped images: a low-frequency pattern per class (random
    frequencies and phases per channel) plus Gaussian noise."""
    k = seeds.key(w, 1)
    kf, kp, kl = jax.random.split(jax.random.fold_in(k, 0), 3)
    freq = jax.random.uniform(kf, (n_classes, 3, 2), minval=1.0, maxval=4.0)
    phase = jax.random.uniform(kp, (n_classes, 3), maxval=2 * np.pi)
    yy, xx = jnp.mgrid[0:32, 0:32] / 32.0
    protos = jnp.sin(2 * np.pi * (freq[..., 0, None, None] * xx
                                  + freq[..., 1, None, None] * yy)
                     + phase[..., None, None])  # [C, 3, 32, 32]
    protos = protos.transpose(0, 2, 3, 1)
    out = []
    for i in range(n):
        ki = jax.random.fold_in(k, i + 1)
        labels = jax.random.randint(jax.random.fold_in(ki, 0), (batch,), 0,
                                    n_classes)
        noise = jax.random.normal(jax.random.fold_in(ki, 1),
                                  (batch, 32, 32, 3))
        out.append(protos[labels] + 0.35 * noise)
    return tuple(out)


class ResNetCell:
    def __init__(self, config, traffic, seed, devices):
        from repro.kernels import dispatch
        from repro.models import resnet

        self.config, self.traffic = config, traffic
        self.cfg = cfg = resnet_config(config)
        self.batch = traffic["batch"]
        self.units_per_call = self.batch
        w = seeds.words(seed)
        self.params, self.bn = jax.jit(make_params, static_argnums=(1, 2, 3))(
            w, cfg.widths, cfg.blocks_per_stage, cfg.n_classes)
        want = jax.eval_shape(lambda: resnet.init(jax.random.PRNGKey(0), cfg))
        got = jax.eval_shape(lambda: (self.params, self.bn))
        if jax.tree.structure(want) != jax.tree.structure(got):
            raise RuntimeError("seeded weights do not match the program's "
                               "parameter layout")
        self.images = jax.jit(make_images, static_argnums=(1, 2, 3))(
            w, traffic["distinct_batches"], self.batch, cfg.n_classes)
        # One program for the whole plan; run eagerly, conv by conv, it
        # is some 220 small programs to load at every set-up.
        self.planned = jax.jit(resnet.plan_params, static_argnums=1)(
            self.params, cfg.cim)

        def resnet_forward(p, b, x):
            return resnet.forward(p, b, x, cfg)[0]

        self.fwd = jax.jit(resnet_forward)
        with dispatch.record_resolutions() as log:
            jax.block_until_ready(self.fwd(self.planned, self.bn,
                                           self.images[0]))
        counts: dict = {}
        for r in log:
            k = (r.key.backend, r.key.shape_cell, r.source)
            counts[k] = counts.get(k, 0) + 1
        for (backend, cell, source), c in sorted(counts.items()):
            print(f"  route {backend} cell={cell} source={source} x{c}",
                  flush=True)
        bad = [r for r in log if r.source == "guard-fallback"]
        if bad:
            raise RuntimeError(f"{len(bad)} guard-fallback route(s): {bad}")

    def call(self, i: int) -> jax.Array:
        x = self.images[i % len(self.images)]
        return jax.block_until_ready(self.fwd(self.planned, self.bn, x))

    def work(self) -> dict:
        macs = work_lib.resnet_macs_per_image(
            self.cfg.widths, self.cfg.blocks_per_stage, self.cfg.n_classes)
        return {"flops_per_call": 2.0 * macs * self.batch,
                "programs": {"forward": "jit_resnet_forward"}}

    def release(self) -> None:
        del self.planned, self.fwd
        gc.collect()

    def logit_err(self, samples, dt=jnp.float32) -> float:
        """Widest |program - reference| logit over the sampled calls, as
        a share of the reference's largest |logit| in that call."""
        worst = 0.0
        for i, logits in samples:
            ref = resnet_ref.forward(
                self.params, self.bn, self.images[i % len(self.images)],
                widths=self.cfg.widths, blocks=self.cfg.blocks_per_stage,
                m=macro_ref.Macro.from_config(self.config["macro"]), dt=dt)
            err = jnp.max(jnp.abs(logits - ref)) / jnp.max(jnp.abs(ref))
            worst = max(worst, float(err))
        return worst

    def control_err(self, samples) -> float:
        """The reference one precision lower (bfloat16), against the
        reference."""
        worst = 0.0
        for i, _ in samples:
            low = resnet_ref.forward(
                self.params, self.bn, self.images[i % len(self.images)],
                widths=self.cfg.widths, blocks=self.cfg.blocks_per_stage,
                m=macro_ref.Macro.from_config(self.config["macro"]),
                dt=jnp.bfloat16)
            worst = max(worst, self.logit_err([(i, low)]))
        return worst

    def check(self, samples) -> list[Check]:
        return [Check("logit_err", self.logit_err(samples),
                      self.traffic["limits"]["logit_err"])]


def setup(config, traffic, seed, devices):
    return ResNetCell(config, traffic, seed, devices)
