"""``bench/scopes.py`` on recorded traces of ``mnist_2nn.dfedsgpsm_k10``'s
5-round supersteps on one TPU v5 lite: ``trace_mnist_scoped.json`` from a
program with the five named scopes and ``FLTrainer.fit``'s ``fl.*`` spans
(its ``paths`` hold each operation's op name path, read from the trace by
``scopes.op_paths``), and ``trace_mnist_2supersteps.json`` from a program
without them."""
from __future__ import annotations

import json
import os
import types

import pytest

from bench import devtrace, harness, scopes
from bench.tests.helpers import BENCH, ROOT

DATA = os.path.join(BENCH, "tests", "data")
CELL = "mnist_2nn.dfedsgpsm_k10"
SPANS = ("fl.superstep", "fl.dispatch", "fl.fetch", "fl.records")


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        d = json.load(f)
    return devtrace.from_dict(d), d.get("paths", {})


def _supersteps(t):
    return [s[2] for s in t.spans].count(devtrace.SUPERSTEP_SPAN)


def _xla_ops_ms(t, rounds):
    cell = harness.resolve(ROOT, CELL)
    reader = dict((m["name"], r) for m, r in cell.per_layer)["xla_ops_ms"]
    return reader.read(types.SimpleNamespace(trace=t, rounds=rounds))


@pytest.fixture(scope="module")
def scoped():
    return _load("trace_mnist_scoped.json")


def test_scoped_trace_holds_paths_and_spans(scoped):
    t, paths = scoped
    n = _supersteps(t)
    assert n >= 1
    assert t.kernels == ["fused_update_bank.7", "gossip_gather.9"]
    assert len(devtrace.module_runs(t, 0)) == n
    assert set().union(*map(scopes.scopes_of, paths.values())) == set(
        scopes.SCOPES)
    steps = [h for h in t.host if h[2] == "fl.superstep"]
    assert len(steps) == n
    for lo, hi, _ in steps:
        inner = [h[2] for h in t.host if lo <= h[0] and h[1] <= hi
                 and h[2] in SPANS[1:]]
        assert inner == list(SPANS[1:])


def test_scopes_split_xla_ops(scoped):
    t, paths = scoped
    rounds = 5 * _supersteps(t)
    split = {s: scopes.scope_ns(t, paths, s) / rounds / 1e6
             for s in scopes.SCOPES + (None,)}
    assert all(v >= 0 for v in split.values())
    assert min(split[s] for s in scopes.SCOPES) > 0
    xla = _xla_ops_ms(t, rounds)
    assert sum(split.values()) == pytest.approx(xla, rel=1e-9)
    assert split[None] < 0.05 * xla


def test_span_means_are_the_spans_durations(scoped):
    t, _ = scoped
    lo, hi = t.window
    for span in SPANS:
        durs = [b - a for a, b, n in t.host
                if n == span and lo <= a and b <= hi]
        assert durs
        assert scopes.span_mean_ns(t, span) == pytest.approx(
            sum(durs) / len(durs))
    assert scopes.span_mean_ns(t, "fl.nothing") is None


def test_trace_without_scopes_reads_nothing():
    t, paths = _load("trace_mnist_2supersteps.json")
    assert paths == {}
    for name in scopes.SCOPES + (None,):
        assert scopes.scope_ns(t, paths, name) is None
    assert scopes.span_mean_ns(t, "fl.dispatch") is None


def test_scopes_of_peels_transform_wrappers():
    path = ("jit(<lambda>)/while/body/closed_call/while/body/closed_call/"
            "sam_grad/vmap(transpose(jvp(jit(log_softmax))))/mul")
    assert scopes.scopes_of(path) == {"sam_grad"}
    assert scopes.scopes_of("a/vmap(transpose(grad_ravel))/b") == {
        "grad_ravel"}
    assert scopes.scopes_of("jit(f)/evaluate/mixer/add") == set()
    assert scopes.scopes_of("") == set()


def test_op_without_a_path_takes_the_next_ops_scope():
    t = devtrace.Trace(
        ops={0: [(0, 1, "fusion.1"), (1, 3, "custom-call.2"),
                 (3, 6, "dynamic-update-slice.3"),
                 (6, 7, "dynamic-update-slice.4"), (7, 9, "copy.5")]},
        modules={0: []}, spans=[], host=[], window=(0, 9))
    paths = {"fusion.1": "jit(f)/sam_grad/dot_general",
             "custom-call.2": "jit(f)/while/body/closed_call/while",
             "dynamic-update-slice.4": "jit(f)/grad_ravel/concatenate"}
    assert [s for *_, s in scopes.op_scopes(t, paths, 0)] == [
        {"sam_grad"}, {"grad_ravel"}, {"grad_ravel"}, {"grad_ravel"},
        set()]
    assert scopes.scope_ns(t, paths, "sam_grad") == 1
    assert scopes.scope_ns(t, paths, "grad_ravel") == 6
    assert scopes.scope_ns(t, paths, None) == 2
    assert scopes.scope_ns(t, paths, "eval") == 0


def _pb(field, value):
    """One protobuf field: a varint for an int, else length-delimited."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(field << 3 | 2) + varint(len(value)) + value


def test_op_paths_read_from_event_metadata(tmp_path):
    """The path is the ``tf_op`` stat of an event's metadata; only the
    planes asked for count."""
    def plane(name, op, path):
        stat = _pb(1, 7) + _pb(5, path + ":")
        meta = _pb(1, 3) + _pb(2, f"%{op} = f32[8]{{0}} add(...)") + _pb(
            5, _pb(1, 2) + _pb(4, 11)) + _pb(5, stat)
        return _pb(1, _pb(2, name) + _pb(3, _pb(2, "XLA Ops"))
                   + _pb(4, _pb(1, 3) + _pb(2, meta))
                   + _pb(5, _pb(1, 7) + _pb(2, _pb(1, 7) + _pb(2, "tf_op")))
                   + _pb(5, _pb(1, 2) + _pb(2, _pb(1, 2) + _pb(2, "x"))))

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(plane("/device:TPU:0", "add.3", "jit(f)/sam_grad/add")
                     + plane("/device:TPU:1", "mul.4", "jit(f)/mix/mul")
                     + plane("/host:CPU", "sub.5", "jit(f)/eval/sub"))
    got = scopes.op_paths(str(path), lambda n: n == "/device:TPU:0")
    assert got == {"add.3": "jit(f)/sam_grad/add"}
