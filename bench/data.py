"""Inputs of a cell, made from ``--seed``: synthetic data and its partition.

Copies of the program's recipes, kept here so that the benchmark owns its
inputs (the program receives only the arrays):

* the Gaussian-mixture classification data of ``repro.data.synthetic``
  (class means and per-class wobble directions on unit vectors, scaled by
  the margin, plus unit Gaussian noise, through ``tanh``), drawn with
  ``jax.random`` on the device rather than with host numpy;
* the Dirichlet label-skew partition of ``repro.data.dirichlet`` (Hsu et
  al. 2019) and its wrap-fill stacking to a fixed number of samples per
  client, on the host over the labels only; the rows are gathered on the
  device.
"""
from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["seed_key", "sub_seed", "make_dataset", "dirichlet_partition",
           "wrap_fill", "client_inputs"]


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from a seed of any size (``PRNGKey`` alone keeps only the
    low 32 bits), folded with a stream number so that data, weights and the
    program's round chain draw from separate streams."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def sub_seed(seed: int, stream: str) -> int:
    """A non-negative 31-bit integer seed derived from ``seed``, for APIs
    that take an int (the program's ``seed=``, numpy's partition)."""
    h = hashlib.sha256(f"{seed}:{stream}".encode()).digest()
    return int.from_bytes(h[:4], "little") & 0x7FFFFFFF


@functools.partial(jax.jit, static_argnames=("dim", "n_classes", "margin"))
def _geometry(key, *, dim, n_classes, margin):
    """Class means and per-class wobble directions: unit vectors, the
    means scaled by the margin."""
    kb, kw = jax.random.split(key)

    def unit(k):
        v = jax.random.normal(k, (n_classes, dim), jnp.float32)
        return v / jnp.linalg.norm(v, axis=1, keepdims=True)

    return margin * unit(kb), unit(kw)


@functools.partial(jax.jit, static_argnames=("n", "shape", "n_classes"))
def _sample(key, means, wobble, *, n, shape, n_classes):
    """``n`` rows: a class, its mean, 1.5 times a Gaussian coefficient
    along its wobble direction and unit Gaussian noise, through tanh."""
    ky, kc, kx = jax.random.split(key, 3)
    y = jax.random.randint(ky, (n,), 0, n_classes, jnp.int32)
    coef = jax.random.normal(kc, (n, 1), jnp.float32)
    x = means[y] + 1.5 * coef * wobble[y] + jax.random.normal(
        kx, (n, means.shape[1]), jnp.float32)
    return {"x": jnp.tanh(x).reshape((n,) + shape), "y": y}


def make_dataset(seed: int, ds: dict):
    """(train, test) of the configuration's dataset.  The class geometry and
    the test split are the dataset's own, drawn from its fixed
    ``ds["seed"]`` (a benchmark's test split does not change from run to
    run); the training rows are drawn from ``seed``."""
    shape, k = tuple(ds["shape"]), ds["n_classes"]
    kg, kt = jax.random.split(seed_key(ds["seed"], 0))
    means, wobble = _geometry(kg, dim=int(np.prod(shape)), n_classes=k,
                              margin=float(ds["margin"]))
    test = _sample(kt, means, wobble, n=ds["n_test"], shape=shape,
                   n_classes=k)
    train = _sample(seed_key(seed, 1), means, wobble, n=ds["n_train"],
                    shape=shape, n_classes=k)
    return train, test


def dirichlet_partition(labels: np.ndarray, n_clients: int, alpha: float,
                        seed: int, min_per_client: int = 2):
    """Split sample indices across clients with Dir(alpha) label skew; the
    recipe of ``repro.data.dirichlet.dirichlet_partition``."""
    rng = np.random.default_rng(seed)
    client_idx = [[] for _ in range(n_clients)]
    for c in np.unique(labels):
        idx_c = rng.permutation(np.where(labels == c)[0])
        props = rng.dirichlet(np.full(n_clients, alpha))
        cuts = (np.cumsum(props)[:-1] * len(idx_c)).astype(int)
        for cid, shard in enumerate(np.split(idx_c, cuts)):
            client_idx[cid].extend(shard.tolist())
    sizes = np.array([len(ci) for ci in client_idx])
    for cid in np.where(sizes < min_per_client)[0]:
        donor = int(np.argmax([len(ci) for ci in client_idx]))
        need = min_per_client - len(client_idx[cid])
        client_idx[cid].extend(client_idx[donor][-need:])
        del client_idx[donor][-need:]
    return [np.sort(np.array(ci, dtype=np.int64)) for ci in client_idx]


def wrap_fill(parts, m: int) -> np.ndarray:
    """(n_clients, m) row indices: each shard wrapped to length ``m``, the
    stacking of ``repro.data.dirichlet.stack_client_data``."""
    return np.stack([np.resize(p, m) for p in parts]).astype(np.int32)


def client_inputs(seed: int, config: dict):
    """Client-stacked train data ``{"x": (n, m, ...), "y": (n, m)}`` and the
    test set, on the device, for one configuration and seed."""
    fed = config["federation"]
    train, test = make_dataset(seed, config["dataset"])
    labels = np.asarray(jax.device_get(train["y"]))
    parts = dirichlet_partition(labels, fed["n_clients"],
                                fed["dirichlet_alpha"],
                                sub_seed(seed, "partition"))
    rows = jnp.asarray(wrap_fill(parts, fed["samples_per_client"]))
    return _gather(train, rows), test


@jax.jit
def _gather(table, rows):
    return {k: v[rows] for k, v in table.items()}
