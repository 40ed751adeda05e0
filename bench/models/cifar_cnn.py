"""Plain reference of the CIFAR-10 CNN of arXiv 2310.05093, Appendix A.

conv5x5(64) - relu - maxpool2 - conv5x5(64) - relu - maxpool2 - fc384 - relu
- fc192 - relu - fc(n_classes), SAME padding, NHWC images, HWIO kernels,
He-normal weights and zero biases.  Written from the paper's description in
plain ``jax.numpy``; it imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _widths(config):
    h, w, c = config["dataset"]["shape"]
    return h, w, c, (h // 4) * (w // 4) * 64, config["dataset"]["n_classes"]


def init(key, config):
    h, w, c, flat, k = _widths(config)
    ks = jax.random.split(key, 5)

    def conv(kk, kh, kw, cin, cout):
        s = float(np.sqrt(2.0 / (kh * kw * cin)))
        return {"w": s * jax.random.normal(kk, (kh, kw, cin, cout)),
                "b": jnp.zeros((cout,), jnp.float32)}

    def dense(kk, nin, nout):
        s = float(np.sqrt(2.0 / nin))
        return {"w": s * jax.random.normal(kk, (nin, nout)),
                "b": jnp.zeros((nout,), jnp.float32)}

    return {"conv1": conv(ks[0], 5, 5, c, 64),
            "conv2": conv(ks[1], 5, 5, 64, 64),
            "fc1": dense(ks[2], flat, 384),
            "fc2": dense(ks[3], 384, 192),
            "out": dense(ks[4], 192, k)}


def apply(params, x, precision=None):
    def conv(x, p):
        y = lax.conv_general_dilated(
            x, p["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
        return y + p["b"]

    def pool(x):
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")

    def dense(x, p):
        return jnp.dot(x, p["w"], precision=precision) + p["b"]

    x = pool(jax.nn.relu(conv(x, params["conv1"])))
    x = pool(jax.nn.relu(conv(x, params["conv2"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(dense(x, params["fc1"]))
    x = jax.nn.relu(dense(x, params["fc2"]))
    return dense(x, params["out"])


def layers(config):
    """Per-example forward multiply-adds of each matmul/conv layer, and
    whether training needs its input gradient (not for the first layer)."""
    h, w, c, flat, k = _widths(config)
    return [
        ("conv1", h * w * 64 * 5 * 5 * c, False),
        ("conv2", (h // 2) * (w // 2) * 64 * 5 * 5 * 64, True),
        ("fc1", flat * 384, True),
        ("fc2", 384 * 192, True),
        ("out", 192 * k, True),
    ]
