"""The reduction from a device trace to the per-layer metrics, checked on a
small recorded trace: two 5-round supersteps of ``mnist_2nn.dfedsgpsm_k10``
traced on one TPU v5 lite (leaf operations, program executions, the
benchmark's spans and the host events over the longest idle gaps)."""
from __future__ import annotations

import json
import os
import types

import pytest

from bench import compare, devtrace, harness, work
from bench.tests.helpers import BENCH, ROOT

FIXTURE = os.path.join(BENCH, "tests", "data", "trace_mnist_2supersteps.json")
CELL = "mnist_2nn.dfedsgpsm_k10"


@pytest.fixture(scope="module")
def trace():
    with open(FIXTURE) as f:
        return devtrace.from_dict(json.load(f))


@pytest.fixture(scope="module")
def run(trace):
    cell = harness.resolve(ROOT, CELL)
    model = harness.load_module(cell.model_path, "m")
    return types.SimpleNamespace(
        trace=trace, rounds=10, cell=cell,
        peaks=work.peaks_for("TPU v5 lite"), chips=1, n=100, dim=199_210,
        itemsize=4, layers=model.layers(cell.config), k_max=11)


def _sum(trace, prefix):
    lo, hi = trace.window
    return sum(b - a for a, b, n in trace.ops[0]
               if n.startswith(prefix) and a >= lo and b <= hi)


def test_recorded_trace_holds_both_kernels(trace):
    assert trace.kernels == ["fused_update_bank.7", "gossip_gather.9"]
    assert len(devtrace.module_runs(trace, 0)) == 2
    assert [s[2] for s in trace.spans].count("bench.superstep") == 2


def test_busy_and_idle_cover_the_window(trace):
    busy = devtrace.busy_ns(trace, 0)
    idle = sum(b - a for a, b, _ in devtrace.idle_gaps(trace, 0))
    assert busy + idle == pytest.approx(trace.window_ns, abs=1.0)
    assert 0 < busy < trace.window_ns


def test_union_and_leaves():
    assert devtrace.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert devtrace.union([(0, 10)], 2, 4) == [[2, 4]]
    loop = [(0, 10, "while.1"), (1, 3, "fusion.1"), (3, 9, "while.2"),
            (4, 5, "custom-call.1"), (6, 9, "fusion.2"), (11, 12, "copy.1")]
    assert [e[2] for e in devtrace.leaves(loop)] == [
        "fusion.1", "custom-call.1", "fusion.2", "copy.1"]
    assert devtrace.op_name("%gossip_gather.9 = f32[100,8]{1,0} custom-call("
                            "...)") == "gossip_gather.9"
    assert devtrace.kernel_name("fused_update_bank.7") == "fused_update_bank"


def test_readers_on_the_recorded_trace(trace, run):
    values = {m["name"]: reader.read(run)
              for m, reader in harness.resolve(ROOT, CELL).per_layer}
    assert set(values) == {"idle_frac", "boundary_gap_ms", "round_mfu",
                           "xla_ops_ms", "update_ms", "update_roofline",
                           "gossip_ms", "gossip_roofline"}
    assert values["update_ms"] == pytest.approx(
        _sum(trace, "fused_update_bank") / 10 / 1e6)
    assert values["gossip_ms"] == pytest.approx(
        _sum(trace, "gossip_gather") / 10 / 1e6)
    ops = sum(devtrace.op_totals(trace, 0).values())
    assert (values["xla_ops_ms"] + values["update_ms"]
            + values["gossip_ms"]) == pytest.approx(ops / 10 / 1e6)
    for name in ("update_roofline", "gossip_roofline", "round_mfu"):
        assert 0 < values[name] <= 100
    assert 0 <= values["idle_frac"] < 100
    busy = devtrace.busy_ns(trace, 0)
    assert values["idle_frac"] == pytest.approx(
        100 * (1 - busy / trace.window_ns))
    assert values["boundary_gap_ms"] > 0


def test_breakdown(trace):
    b = compare.breakdown(trace)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert b["device_ops"][0][0] == "fused_update_bank.7"
    for name, s in b["idle_gaps"]:
        assert name.startswith(("in jit_", "host: ")) and s > 0
