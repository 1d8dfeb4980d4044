"""Plain float32 reference of the CIFAR ResNet (He et al. 2016, section 4.2).

Straightforward jax.numpy from the published description: a 3x3 stem, then
three stages of (depth - 2) / 6 basic blocks of two 3x3 convolutions at the
configuration's widths, a strided first block per later stage with a 1x1
projection shortcut, global average pooling and a linear head. The one
departure the configuration states: GroupNorm over ``norm_groups`` groups
where the paper has BatchNorm. Imports nothing of the system under test.

``init`` draws the initial weights from the key in the same way as the
configuration's system does (He-normal convolutions truncated at two
standard deviations, unit norm scales and zero biases, a normal head scaled
by 1/sqrt(width)), so that both start from one point given one seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _conv_w(key, kh, kw, cin, cout):
    std = (2.0 / (kh * kw * cin)) ** 0.5
    return std * jax.random.truncated_normal(key, -2.0, 2.0, (kh, kw, cin, cout),
                                             jnp.float32)


def _norm(c):
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}


def blocks(cfg):
    """(name, in width, out width, stride) of every basic block."""
    n = (cfg["depth"] - 2) // 6
    cin, out = cfg["widths"][0], []
    for s, w in enumerate(cfg["widths"]):
        for b in range(n):
            out.append((f"s{s}b{b}", cin, w, 2 if (s > 0 and b == 0) else 1))
            cin = w
    return out


def init(key, cfg):
    bl = blocks(cfg)
    keys = jax.random.split(key, len(bl) + 2)
    w0, wl = cfg["widths"][0], cfg["widths"][-1]
    params = {"stem": _conv_w(keys[0], 3, 3, cfg["image_shape"][2], w0),
              "stem_gn": _norm(w0)}
    for i, (name, cin, cout, stride) in enumerate(bl):
        k1, k2, k3 = jax.random.split(keys[1 + i], 3)
        p = {"conv1": _conv_w(k1, 3, 3, cin, cout), "gn1": _norm(cout),
             "conv2": _conv_w(k2, 3, 3, cout, cout), "gn2": _norm(cout)}
        if stride != 1 or cin != cout:
            p["proj"] = _conv_w(k3, 1, 1, cin, cout)
        params[name] = p
    params["head"] = {
        "kernel": jax.random.normal(keys[len(bl) + 1], (wl, cfg["num_classes"])) * wl ** -0.5,
        "bias": jnp.zeros((cfg["num_classes"],)),
    }
    return params


def _conv(x, w, stride, precision):
    return lax.conv_general_dilated(x, w.astype(x.dtype), (stride, stride), "SAME",
                                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                                    precision=precision)


def _group_norm(p, x, groups, eps=1e-5):
    n, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(n, h, w, g, c // g)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + eps)
    return xg.reshape(n, h, w, c) * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def forward(params, x, cfg, precision):
    """Logits (batch, classes) of images ``x`` (batch, H, W, C), computed
    in the dtype of ``x``."""
    groups = cfg["norm_groups"]
    h = jax.nn.relu(_group_norm(params["stem_gn"], _conv(x, params["stem"], 1, precision),
                                groups))
    for name, _, _, stride in blocks(cfg):
        p = params[name]
        y = jax.nn.relu(_group_norm(p["gn1"], _conv(h, p["conv1"], stride, precision), groups))
        y = _group_norm(p["gn2"], _conv(y, p["conv2"], 1, precision), groups)
        shortcut = _conv(h, p["proj"], stride, precision) if "proj" in p else h
        h = jax.nn.relu(y + shortcut)
    h = jnp.mean(h, axis=(1, 2))
    head = params["head"]
    return (jnp.dot(h, head["kernel"].astype(h.dtype), precision=precision)
            + head["bias"].astype(h.dtype))




def loss(params, batch, cfg, dtype, precision, keep=None):
    """Mean cross-entropy of one client's batch, over its first ``keep``
    images (all where None), at matmul ``precision``. A ``dtype`` below
    float32 casts weights and inputs to it and runs the model there (the
    precision control)."""
    x, y = batch
    p = jax.tree.map(lambda w: w.astype(dtype), params)
    logits = forward(p, x.astype(dtype), cfg, precision).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)
    return jnp.mean(nll[:keep])
