"""The yardstick's operations and bytes, counted from shapes, and the rule
that a roofline's required work does not depend on the kernel doing it."""
from __future__ import annotations

import json
import os
import types

import pytest

from bench import devtrace, harness, work
from bench.tests.helpers import BENCH, ROOT


def _cell(name):
    return harness.resolve(ROOT, name)


def _layers(cell):
    return harness.load_module(cell.model_path, "m").layers(cell.config)


def test_cifar_cnn_flops_per_round():
    cell = _cell("cifar10_cnn.dfedsgpsm_k10")
    macs = [32 * 32 * 64 * 75, 16 * 16 * 64 * 1600, 4096 * 384, 384 * 192,
            192 * 10]
    assert [m for _, m, _ in _layers(cell)] == macs
    fwd = 2 * sum(macs)
    bwd = 2 * sum(macs) + 2 * sum(macs[1:])  # no input gradient for conv1
    train = 100 * 5 * 32 * 2 * (fwd + bwd)
    want = train + 10_000 * fwd / 5
    got = work.train_flops_per_round(_layers(cell), cell.config, cell.traffic)
    assert got == pytest.approx(want, rel=1e-12)
    assert 6.0e12 < got < 6.2e12


def test_mnist_2nn_flops_and_params_match_the_config():
    cell = _cell("mnist_2nn.dfedsgpsm_k10")
    macs = [m for _, m, _ in _layers(cell)]
    assert macs == [784 * 200, 200 * 200, 200 * 10]
    params = sum(macs) + 200 + 200 + 10
    assert params == cell.config["params"] == 199_210


def test_cifar_params_match_the_config():
    import jax

    cell = _cell("cifar10_cnn.dfedsgpsm_k10")
    model = harness.load_module(cell.model_path, "m")
    shapes = jax.eval_shape(lambda k: model.init(k, cell.config),
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 1_756_426


def test_update_and_gossip_work():
    assert work.update_work(100, 1000) == (4.0e5, 100 * 1000 * 20.0)
    assert work.gossip_work(100, 11, 1000) == (2.0 * 100 * 11 * 1000,
                                               2.0 * 100 * 1000 * 4)
    peaks = work.peaks_for("TPU v5 lite")
    share, bound = work.roofline(0.0, 819e9, 2.0, peaks)
    assert bound == "bytes" and share == pytest.approx(50.0)
    share, bound = work.roofline(197e12, 1.0, 4.0, peaks)
    assert bound == "flops" and share == pytest.approx(25.0)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks_for("TPU v9 imaginary")


def _ctx(op_name, dur_ns, rounds=5):
    ops = [(0.0, 1000.0, "fusion.1"), (1000.0, 1000.0 + dur_ns, op_name)]
    kernel = devtrace.kernel_name(op_name) in (
        "gossip_gather", "gossip_matmul", "fused_update_bank")
    t = devtrace.Trace({0: ops}, {0: [(0.0, 1000.0 + dur_ns, "jit_step")]},
                       [(0.0, 2000.0 + dur_ns, "bench.superstep")], [],
                       (0.0, 2000.0 + dur_ns), [op_name] if kernel else [])
    cell = _cell("cifar10_cnn.dfedsgpsm_k10")
    return types.SimpleNamespace(
        trace=t, rounds=rounds, cell=cell,
        peaks=work.peaks_for("TPU v5 lite"), chips=1, n=100, dim=1_756_426,
        itemsize=4, layers=_layers(cell), k_max=11)


def test_gossip_roofline_does_not_depend_on_the_kernel():
    reader = harness.load_module(
        os.path.join(BENCH, "metrics", "gossip_roofline.py"), "g")
    sparse = reader.read(_ctx("gossip_gather.3", 5e7))
    dense = reader.read(_ctx("gossip_matmul.7", 5e7))
    assert sparse == dense
    # 5 rounds of 1.405 GB each in 50 ms at 819 GB/s.
    assert sparse == pytest.approx(100 * 5 * 2 * 100 * 1_756_426 * 4
                                   / 819e9 / 0.05)
    assert reader.read(_ctx("fusion.9", 5e7)) is None


def test_readers_return_nothing_without_their_kernel():
    for name in ("update_ms", "update_roofline", "gossip_ms",
                 "gossip_roofline"):
        reader = harness.load_module(
            os.path.join(BENCH, "metrics", f"{name}.py"), name)
        assert reader.read(_ctx("convolution.2", 1e6)) is None


def test_benchmark_names_a_reader_for_every_per_layer_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for m in spec["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           f"{m['name']}.py"))
