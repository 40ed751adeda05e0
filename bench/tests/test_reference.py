"""The reference's memory: clients in blocks written into one donated bank.

Its readings do not depend on how many clients a block holds, and at the
paper's ResNet-18-GN widths its round compiles to well under one v5e's
memory (CPU, ahead of time, from shapes alone)."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

from bench import data, harness, reference, run
from bench.tests.helpers import ROOT, make_root

READINGS = ("losses", "client_losses", "v", "dx", "w", "test_loss")


@pytest.mark.parametrize("dim, block", [
    (199_210, 25),      # mnist_2nn.n100
    (1_756_426, 25),    # cifar10_cnn.n100
    (11_224_932, 5),    # resnet18_gn, CIFAR-100 head: 45 MB a row
    (80_000_000, 1),    # one row over BLOCK_BYTES
])
def test_block_rule(dim, block):
    assert reference.client_block(100, 4 * dim) == block


def test_readings_do_not_depend_on_the_block(tmp_path, monkeypatch):
    root = make_root(str(tmp_path))
    cell = harness.resolve(root, "mnist_2nn.tiny_k2")
    cfg, seed = cell.config, 3000000031
    assert cfg["federation"]["n_clients"] == 8
    model = harness.load_module(cell.model_path, cfg["model"])
    clients, test = data.client_inputs(seed, cfg)
    params0 = model.init(data.seed_key(seed, 2), cfg)
    key = data.seed_key(seed, 3)
    out = {}
    for block in (2, 8):
        monkeypatch.setattr(reference, "BLOCK", block)
        out[block] = reference.run_check(
            model, cfg, cell.traffic, run.check_rounds(cell.traffic),
            params0, key, clients, test)
    for k in READINGS:
        np.testing.assert_allclose(out[2][k], out[8][k], rtol=1e-6,
                                   err_msg=k)


GB = 1e9


def test_round_fits_at_resnet18_gn_widths():
    """One round of the check at n=100, D=11,224,932 (ResNet-18-GN with a
    CIFAR-100 head), 512 CIFAR-shaped samples a client, K=5, B=32: what
    the compiled round holds at once (arguments, outputs not aliased to
    them, temporaries) stays under 12 GB of a v5e's 15.75.  The program's
    ``resnet18_gn`` stands in as the model, for its sizes only."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.models.small import resnet18_gn

    n, m = 100, 512
    net = resnet18_gn(n_classes=100)
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree.leaves(shapes)) == 11_224_932
    alg = {"local_steps": 5, "batch_size": 32, "rho": 0.1, "momentum": 0.9,
           "lr": 0.01, "lr_decay": 0.998}
    round_fn = reference.make_round(
        lambda p, x, precision: net.apply(p, x), alg, n, 10,
        dtype=jnp.float32, precision=lax.Precision.HIGHEST)
    spec = jax.ShapeDtypeStruct
    X = jax.tree.map(lambda a: spec((n,) + a.shape, jnp.float32), shapes)
    batch = {"x": spec((n, m, 32, 32, 3), jnp.float32),
             "y": spec((n, m), jnp.int32)}
    mem = round_fn.lower(X, spec((n,), jnp.float32), spec((2,), jnp.uint32),
                         spec((), jnp.int32), batch).compile(
                             ).memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert held <= 12 * GB, (
        f"arguments {mem.argument_size_in_bytes / GB:.2f} GB, outputs "
        f"{mem.output_size_in_bytes / GB:.2f}, aliased "
        f"{mem.alias_size_in_bytes / GB:.2f}, temporaries "
        f"{mem.temp_size_in_bytes / GB:.2f}")
