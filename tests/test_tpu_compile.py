"""The main path's Mosaic kernels compile for a TPU v5e at the paper models'
full widths.

Interpret mode runs a kernel body as plain jnp and so never shows what the
TPU compiler refuses (a vectorized row gather inside a kernel, unaligned
slices, too much VMEM).  These tests compile each kernel ahead of time for
a described — not attached — v5e chip and assert that the program holds the
kernel as a ``tpu_custom_call``.  The topology is described inside a
fixture, never at import: only the worker that runs this file loads the
TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ref
from repro.kernels.fused_update import fused_update_bank_pallas
from repro.kernels.gossip_gather import gossip_gather_pallas
from repro.kernels.gossip_matmul import gossip_matmul_pallas

D_MNIST_2NN = 199_210
D_CIFAR_CNN = 1_756_426


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # The TPU compiler logs under /tmp unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _mosaic_kernels(compiled) -> set:
    return set(re.findall(
        r"%([A-Za-z_]+)(?:\.\d+)? = [^\n]*"
        r'custom_call_target="tpu_custom_call"', compiled.as_text()))


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n,k_max,D,dtype", [
    (100, 11, D_CIFAR_CNN, jnp.float32),
    (512, 11, D_MNIST_2NN, jnp.float32),
    (512, 11, D_MNIST_2NN, jnp.bfloat16),  # bank_dtype=bf16
])
def test_gossip_gather_compiles_for_v5e(one_chip, no_persistent_cache,
                                        n, k_max, D, dtype):
    compiled = gossip_gather_pallas.lower(
        _spec(one_chip, (n, k_max), jnp.int32),
        _spec(one_chip, (n, k_max)),
        _spec(one_chip, (n, D), dtype),
        interpret=False,
    ).compile()
    assert "gossip_gather" in _mosaic_kernels(compiled)


def test_gossip_matmul_compiles_for_v5e(one_chip, no_persistent_cache):
    n = 16
    compiled = gossip_matmul_pallas.lower(
        _spec(one_chip, (n, n)), _spec(one_chip, (n, D_MNIST_2NN)),
        interpret=False,
    ).compile()
    assert "gossip_matmul" in _mosaic_kernels(compiled)


@pytest.mark.parametrize("n,D,dtype", [
    (100, D_CIFAR_CNN, jnp.float32),
    (100, D_MNIST_2NN, jnp.float32),
    (25, D_CIFAR_CNN, jnp.float32),  # one shard of 100 rows on four chips
    (100, D_MNIST_2NN, jnp.bfloat16),  # bank_dtype=bf16
])
def test_fused_update_bank_compiles_for_v5e(one_chip, no_persistent_cache,
                                            n, D, dtype):
    """The kernel runs on the caller's unpadded banks, in tiles that fit
    the chip's scoped VMEM: no padded copy of a bank, two (n, D) outputs."""
    bank = _spec(one_chip, (n, D), dtype)
    scalar = _spec(one_chip, ())
    compiled = fused_update_bank_pallas.lower(
        bank, _spec(one_chip, (n, D)), bank, scalar, scalar,
        _spec(one_chip, (n,)), interpret=False,
    ).compile()
    text = compiled.as_text()
    assert "fused_update_bank" in _mosaic_kernels(compiled)
    # Every bank-sized buffer is the bank itself: no (104, 1756672) of a
    # padded copy, no pad of anything wider than the two update scalars.
    shapes = set(re.findall(r"\b(?:f32|bf16)\[(\d+),(\d+)\]", text))
    assert {(int(a), int(b)) for a, b in shapes} <= {(n, D), (n, 1)}
    for rank in re.findall(r"= \w+\[([\d,]*)\]\S* pad\(", text):
        assert rank.count(",") == 0, f"pad of a [{rank}] array"
    (outs,) = re.findall(
        r"%fused_update_bank(?:\.\d+)? = \((.*?)\) custom-call\(", text)
    hlo_dtype = {"float32": "f32", "bfloat16": "bf16"}[jnp.dtype(dtype).name]
    assert re.findall(r"(\w+)\[(\d+),(\d+)\]", outs) == [
        (hlo_dtype, str(n), str(D)), ("f32", str(n), str(D))]


@pytest.mark.parametrize("k_max", [1, 11])
def test_gossip_gather_kernel_body_matches_ref(k_max):
    """The Mosaic body, run by the Pallas interpreter over a ragged last
    D-panel (D not a multiple of block_d), against the reference."""
    n, D, block_d = 24, 1000, 256
    rng = np.random.default_rng(k_max)
    idx = jnp.asarray(rng.integers(0, n, (n, k_max)), jnp.int32)
    wgt = jnp.asarray(rng.random((n, k_max)), jnp.float32)
    X = jax.random.normal(jax.random.PRNGKey(k_max), (n, D), jnp.float32)
    got = gossip_gather_pallas(idx, wgt, X, block_d=block_d, interpret=True)
    want = ref.gossip_gather_ref(idx, wgt, X)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
