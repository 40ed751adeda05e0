"""The round's phases carry stable names for the profiler: five named scopes
in the compiled superstep (``sam_grad``, ``grad_ravel``, ``update_pad``,
``mix``, ``eval``), and host spans on ``FLTrainer.fit``'s superstep boundary
(``fl.superstep`` > ``fl.dispatch``, ``fl.fetch``, ``fl.records``).  The
benchmark's per-layer metrics read both from a device trace; the spans must
not add a device read to the host loop."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import FLTrainer, TopologyConfig, make_algo
from repro.data.dirichlet import dirichlet_partition, stack_client_data
from repro.data.synthetic import make_dataset
from repro.models.small import mnist_2nn

SCOPES = ("sam_grad", "grad_ravel", "update_pad", "mix", "eval")
SPANS = ("fl.dispatch", "fl.fetch", "fl.records")


@pytest.fixture(scope="module")
def setting():
    train, test = make_dataset("mnist", 1200, 100, seed=0)
    parts = dirichlet_partition(train["y"], 8, alpha=0.3, seed=0)
    cdata = stack_client_data(train, parts, pad_to=128)
    testj = {k: jnp.asarray(v) for k, v in test.items()}
    return mnist_2nn(), {k: jnp.asarray(v) for k, v in cdata.items()}, testj


def _trainer(setting):
    model, cdata, _ = setting
    algo = make_algo("dfedsgpsm", local_steps=3, batch_size=32)
    topo = TopologyConfig(kind="kout", n_clients=8, k_out=2)
    return FLTrainer(model.loss, model.init, cdata, algo, topo, seed=0)


def _scopes_named(text: str) -> set:
    """The scopes among the name-stack components of the locations in a
    lowered program's debug text, transform wrappers such as
    ``vmap(transpose(...))`` peeled."""
    found = set()
    for path in re.findall(r'loc\("([^"]*)"', text):
        for comp in path.split("/"):
            found.update(re.split(r"[();]", comp))
    return found & set(SCOPES)


def test_superstep_names_every_phase(setting):
    _, _, testj = setting
    tr = _trainer(setting)
    lowered = jax.jit(
        lambda s: tr.program.run_superstep(s, 2, 2, testj)).lower(tr.state)
    assert _scopes_named(lowered.as_text(debug_info=True)) == set(SCOPES)


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    data = ProfileData.from_file(path[0])
    return sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
        for plane in data.planes if plane.name.startswith("/host:CPU")
        for line in plane.lines for e in line.events
        if e.name.startswith("fl."))


def test_fit_spans_each_superstep(setting, tmp_path):
    tr = _trainer(setting)
    with jax.profiler.trace(str(tmp_path)):
        tr.fit(4, superstep=2)
        tr.fit(4, superstep=2)
    events = _host_events(str(tmp_path))
    steps = [e for e in events if e[2] == "fl.superstep"]
    assert [e[3]["step_num"] for e in steps] == [0, 2, 4, 6]
    for lo, hi, _, _ in steps:
        inner = [e for e in events if lo <= e[0] and e[1] <= hi
                 and e[2] in SPANS]
        assert [e[2] for e in inner] == list(SPANS)
        ends = [e[1] for e in inner]
        assert all(end <= nxt[0] for end, nxt in zip(ends, inner[1:]))
    assert sum(e[2] in SPANS for e in events) == 3 * len(steps)


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
def test_fit_reads_the_device_once_a_superstep(setting, tmp_path,
                                               monkeypatch, traced):
    tr = _trainer(setting)
    tr.fit(2, superstep=2)  # compile outside the count
    gets, stray, inside = [], [], [False]
    array_type = type(tr.state.w)
    real_get, real_value = jax.device_get, array_type._value

    def device_get(x):
        gets.append(1)
        inside[0] = True
        try:
            return real_get(x)
        finally:
            inside[0] = False

    def value(self):
        if not inside[0]:
            stray.append(self.shape)
        return real_value.fget(self)

    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(array_type, "_value", property(value))
    if traced:
        with jax.profiler.trace(str(tmp_path)):
            hist = tr.fit(6, superstep=2)
    else:
        hist = tr.fit(6, superstep=2)
    assert len(hist) == 6 and len(gets) == 3
    assert stray == []
