"""Plain reference of DFedSGPSM's first rounds (arXiv 2310.05093, Alg. 1).

Per round t, from the round key: ``split(key, 2 + n)`` gives the next
round key, the topology key and one key per client.  Each client i runs
K local steps on its own data: a minibatch drawn with replacement, the
de-biased model ``z = x / w_i``, the two-pass SAM gradient (perturbation
``rho * g / ||g||`` over the whole model, second gradient on the same
batch), momentum ``v = alpha v + g`` from ``v = 0`` each round and
``x = x - lr_t v`` with ``lr_t = lr * decay**t``.  Then push-sum over the
round's k-in graph (receiver i reads itself and k distinct senders drawn
by a top-k of uniform scores; every edge from sender j carries
``1 / (out_degree(j) + 1)``): ``x_i = sum_j P_ij x_j``, ``w_i = sum_j
P_ij w_j``.  After the last round followed, the consensus model (the mean
of the client rows) is evaluated on the test set.

Written from the algorithm in plain ``jax.numpy`` over parameter pytrees;
it imports nothing of the program and takes none of its arrays.  Its device
memory is one (n, D) bank, donated from round to round, a second for the
mix, and one block of clients' activations: the local phase runs the
clients in blocks (``client_block``) and writes each block's rows back into
the bank in place; a round returns the momentum's per-leaf sums of squares,
not the momentum; the change is read against the initial weights as one
row.  ``dtype`` and ``precision`` select the reference (float32 at
``HIGHEST``) or its control (bfloat16); ``fault`` plants one of the
faults the benchmark's check must catch (``"half_batch"``: the loss is
the mean over the first half of each minibatch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["kin_graph", "run_check", "leaf_norms"]


def kin_graph(key, n: int, k: int):
    """(idx, wgt) of the round's receiver-side k-in graph, slot 0 = self."""
    scores = jax.random.uniform(key, (n, n)) - 2.0 * jnp.eye(n)
    _, picks = lax.top_k(scores, k)
    outdeg = jnp.zeros((n,), jnp.float32).at[picks.reshape(-1)].add(1.0)
    idx = jnp.concatenate(
        [jnp.arange(n, dtype=jnp.int32)[:, None], picks.astype(jnp.int32)], 1)
    return idx, 1.0 / (outdeg + 1.0)[idx]


def _xent(logits, y):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]


def leaf_norms(tree):
    """Frobenius norm of every leaf, in tree order, as one f32 vector."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


BLOCK = 25  # the most clients the reference runs side by side
BLOCK_BYTES = 256 * 2**20  # the most bytes of bank rows in one block


def client_block(n: int, row_bytes: int) -> int:
    """Clients in one block of the local phase: the largest divisor of
    ``n`` that is at most ``BLOCK`` and whose rows of the bank take at most
    ``BLOCK_BYTES`` (one client where a single row is larger)."""
    return max(b for b in range(1, min(n, BLOCK) + 1)
               if n % b == 0 and (b == 1 or b * row_bytes <= BLOCK_BYTES))


def make_round(apply, alg, n, k_out, *, dtype, precision, fault=None):
    K, B = alg["local_steps"], alg["batch_size"]
    rho, alpha = alg["rho"], alg["momentum"]
    lr0, decay = alg["lr"], alg["lr_decay"]

    def loss_fn(params, batch):
        x, y = batch["x"], batch["y"]
        if fault == "half_batch":
            x, y = x[: B // 2], y[: B // 2]
        return jnp.mean(_xent(apply(params, x, precision), y))

    def client(x, w_i, key_i, data_i, lr):
        m = data_i["y"].shape[0]

        def step(carry, _):
            x, v, key_i = carry
            key_i, bk = jax.random.split(key_i)
            rows = jax.random.randint(bk, (B,), 0, m)
            batch = {"x": data_i["x"][rows], "y": data_i["y"][rows]}
            z = jax.tree.map(lambda p: p / w_i, x)
            loss, g1 = jax.value_and_grad(loss_fn)(z, batch)
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                for g in jax.tree.leaves(g1)))
            scale = rho / (norm + 1e-12)
            zp = jax.tree.map(
                lambda p, g: (p.astype(jnp.float32)
                              + scale * g.astype(jnp.float32)).astype(dtype),
                z, g1)
            g2 = jax.grad(loss_fn)(zp, batch)
            v = jax.tree.map(lambda a, g: (alpha * a + g).astype(dtype), v, g2)
            x = jax.tree.map(lambda p, a: (p - lr * a).astype(dtype), x, v)
            return (x, v, key_i), loss

        v0 = jax.tree.map(jnp.zeros_like, x)
        (x, v, _), losses = lax.scan(step, (x, v0, key_i), None, length=K)
        return x, v, jnp.mean(losses)

    def mix(idx, wgt, a):
        P = jnp.zeros((n, n), dtype).at[jnp.arange(n)[:, None], idx].add(
            wgt.astype(dtype))
        flat = a.reshape(n, -1).astype(dtype)
        return jnp.dot(P, flat, precision=precision).reshape(a.shape)

    @functools.partial(jax.jit, donate_argnums=0)
    def round_fn(X, w, key, t, data):
        keys = jax.random.split(key, 2 + n)
        key_next, tkey, ckeys = keys[0], keys[1], keys[2:]
        lr = jnp.float32(lr0) * jnp.float32(decay) ** t.astype(jnp.float32)
        lr = lr.astype(dtype)
        leaves = jax.tree.leaves(X)
        nb = client_block(n, sum(a.size // n * a.dtype.itemsize
                                 for a in leaves))

        def local(b, carry):
            X, v_sq, losses = carry
            start = b * nb

            def rows(a):
                return lax.dynamic_slice_in_dim(a, start, nb)

            xb, vb, lb = jax.vmap(client, in_axes=(0, 0, 0, 0, None))(
                jax.tree.map(rows, X), rows(w), rows(ckeys),
                jax.tree.map(rows, data), lr)
            X = jax.tree.map(
                lambda a, u: lax.dynamic_update_slice_in_dim(a, u, start, 0),
                X, xb)
            v_sq = v_sq + jnp.stack([jnp.sum(jnp.square(a.astype(jnp.float32)))
                                     for a in jax.tree.leaves(vb)])
            losses = lax.dynamic_update_slice_in_dim(
                losses, lb.astype(jnp.float32), start, 0)
            return X, v_sq, losses

        X, v_sq, losses = lax.fori_loop(
            0, n // nb, local,
            (X, jnp.zeros((len(leaves),), jnp.float32),
             jnp.zeros((n,), jnp.float32)))
        idx, wgt = kin_graph(tkey, n, k_out)
        X = jax.tree.map(functools.partial(mix, idx, wgt), X)
        w = mix(idx, wgt, w)
        return X, v_sq, w, key_next, losses

    return round_fn


def make_eval(apply, precision, chunk=1000):
    @jax.jit
    def eval_fn(X, test):
        params = jax.tree.map(lambda a: jnp.mean(a, axis=0), X)
        n = test["y"].shape[0]
        c = max(b for b in range(1, min(n, chunk) + 1) if n % b == 0)

        def part(args):
            x, y = args
            return jnp.sum(_xent(apply(params, x, precision), y))

        sums = lax.map(part, (test["x"].reshape((n // c, c) + test["x"].shape[1:]),
                              test["y"].reshape(n // c, c)))
        return jnp.sum(sums) / n

    return eval_fn


@jax.jit
def _change_norms(X, params0):
    """``leaf_norms`` of the bank's change from the initial weights, the
    weights broadcast over the clients inside the reduction."""
    return leaf_norms(jax.tree.map(
        lambda a, p: (a.astype(jnp.float32)
                      - p.astype(a.dtype).astype(jnp.float32)), X, params0))


def run_check(model, config, traffic, rounds, params0, round_key, data,
              test, *, control=False, fault=None):
    """The reference's first rounds from the seed's initial state.

    ``rounds`` is ``(last, r_grad, r_change)`` (``run.check_rounds``).
    Returns host numbers: ``losses`` of every round; after round
    ``r_grad`` each client's loss (``client_losses``) and the per-leaf
    norms ``v`` of that round's momentum; after ``r_change`` the per-leaf
    norms ``dx`` of the change of the bank; after ``last`` the push-sum
    weights ``w`` and the consensus model's ``test_loss``."""
    alg, fed = config["algorithm"], config["federation"]
    n = fed["n_clients"]
    last, r_grad, r_change = rounds
    dtype = jnp.bfloat16 if control else jnp.float32
    precision = None if control else lax.Precision.HIGHEST
    round_fn = make_round(model.apply, alg, n, traffic["topology"]["k_out"],
                          dtype=dtype, precision=precision, fault=fault)
    X = jax.tree.map(
        lambda p: jnp.broadcast_to(p.astype(dtype), (n,) + p.shape), params0)
    cast = (lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a)
    data = jax.tree.map(cast, data)
    w, key = jnp.ones((n,), dtype), round_key
    losses, out = [], {}
    for t in range(last):
        X, v_sq, w, key, client_losses = round_fn(X, w, key, jnp.int32(t),
                                                  data)
        losses.append(jnp.mean(client_losses))
        if t + 1 == r_grad:
            out.update(v=jnp.sqrt(v_sq), client_losses=client_losses)
        if t + 1 == r_change:
            out["dx"] = _change_norms(X, params0)
    out.update(losses=jnp.stack(losses), w=w.astype(jnp.float32),
               test_loss=make_eval(model.apply, precision)(
                   X, jax.tree.map(cast, test)))
    return {k: jax.device_get(v) for k, v in out.items()}
