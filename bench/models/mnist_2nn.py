"""Plain reference of the MNIST 2NN (784-200-200-10 MLP with ReLU) of
McMahan et al. 2017, as used by arXiv 2310.05093.  He-normal weights, zero
biases; written in plain ``jax.numpy``, importing nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _sizes(config):
    d_in = int(np.prod(config["dataset"]["shape"]))
    return [d_in, 200, 200, config["dataset"]["n_classes"]]


def init(key, config):
    sizes = _sizes(config)
    ks = jax.random.split(key, 3)
    names = ("fc1", "fc2", "out")
    return {
        n: {"w": float(np.sqrt(2.0 / a)) * jax.random.normal(k, (a, b)),
            "b": jnp.zeros((b,), jnp.float32)}
        for n, k, a, b in zip(names, ks, sizes[:-1], sizes[1:])
    }


def apply(params, x, precision=None):
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, params["fc1"]["w"], precision=precision)
                    + params["fc1"]["b"])
    x = jax.nn.relu(jnp.dot(x, params["fc2"]["w"], precision=precision)
                    + params["fc2"]["b"])
    return jnp.dot(x, params["out"]["w"], precision=precision) \
        + params["out"]["b"]


def layers(config):
    """Per-example forward multiply-adds of each layer, and whether
    training needs its input gradient (not for the first layer)."""
    s = _sizes(config)
    return [("fc1", s[0] * s[1], False), ("fc2", s[1] * s[2], True),
            ("out", s[2] * s[3], True)]
