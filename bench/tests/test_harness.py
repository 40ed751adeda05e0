"""The harness finds every part of a cell by name, a new cell needs only new
files and entries, and the measuring path refuses to run without a TPU."""
from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.helpers import BENCH, ROOT, make_root


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", [w["name"] for w in _spec()["workloads"]])
def test_every_cell_resolves(workload):
    cell = harness.resolve(ROOT, workload)
    assert cell.chips in (1, 4)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "round_ms"}
    assert cell.per_layer
    for entry, reader in cell.per_layer:
        assert entry["moves"] == "round_ms" and callable(reader.read)
    # Every number that decides ``correct`` has its limit in the cell's file.
    assert cell.limits["limits"]


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_dummy_cell_needs_only_new_files(tmp_path):
    """A configuration, a traffic mix, a metric and a cell added as new
    files plus entries resolve without an edit to any existing file."""
    root = make_root(str(tmp_path))
    bench = os.path.join(root, "bench")
    before = _digest(bench)
    with open(os.path.join(bench, "configs", "dummy.n4.json"), "w") as f:
        json.dump({"name": "dummy.n4", "model": "mnist_2nn",
                   "dataset": {"seed": 0, "shape": [784], "n_classes": 10,
                               "margin": 4.0, "n_train": 64, "n_test": 16},
                   "federation": {"n_clients": 4, "dirichlet_alpha": 0.3,
                                  "samples_per_client": 16},
                   "algorithm": {"name": "dfedsgpsm", "local_steps": 1,
                                 "batch_size": 4, "rho": 0.1,
                                 "momentum": 0.9, "lr": 0.1,
                                 "lr_decay": 1.0}}, f)
    with open(os.path.join(bench, "traffic", "dummy_mix.json"), "w") as f:
        json.dump({"name": "dummy_mix",
                   "topology": {"kind": "kout", "k_out": 1,
                                "time_varying": True},
                   "links": "perfect", "gossip": "sparse",
                   "superstep_rounds": 1, "eval_every": 1,
                   "loop": "closed"}, f)
    with open(os.path.join(bench, "limits", "dummy.n4.dummy_mix.json"),
              "w") as f:
        json.dump({"limits": {"weights": 1e-5}}, f)
    with open(os.path.join(bench, "metrics", "dummy_metric.py"), "w") as f:
        f.write("def read(run):\n    return None\n")
    added = set(_digest(bench)) - set(before)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "dummy.n4", "source": "test",
                            "file": "bench/configs/dummy.n4.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "dummy.n4.dummy_mix",
                              "config": "dummy.n4", "traffic": "dummy_mix",
                              "chips": 4, "why": "test"})
    spec["per_layer"].append({"name": "dummy_metric", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "test", "moves": "round_ms",
                              "workloads": ["dummy.n4.dummy_mix"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    cell = harness.resolve(root, "dummy.n4.dummy_mix")
    assert cell.chips == 4
    assert cell.config["federation"]["n_clients"] == 4
    assert cell.traffic["topology"]["k_out"] == 1
    assert cell.limits["limits"] == {"weights": 1e-5}
    names = [m["name"] for m, _ in cell.per_layer]
    assert "dummy_metric" in names and "gossip_ms" not in names
    assert cell.model_path.endswith(os.path.join("models", "mnist_2nn.py"))
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == added
    # The existing cells still resolve, and do not report the new metric.
    old = harness.resolve(root, "mnist_2nn.tiny_k2")
    assert "dummy_metric" not in [m["name"] for m, _ in old.per_layer]


@pytest.mark.parametrize("edit, message", [
    (lambda s: s["workloads"][0].update(chips=2), "chips"),
    (lambda s: s["workloads"][0].update(traffic="no_such_mix"),
     "no_such_mix"),
    (lambda s: s["workloads"][0].update(config="no.such"), "no.such"),
    (lambda s: s["per_layer"].append(dict(s["per_layer"][0],
                                          name="no_reader")), "no_reader"),
    (lambda s: s["end_to_end"][0].update(source="program_counter"),
     "source"),
])
def test_bad_entries_are_refused(tmp_path, edit, message):
    root = make_root(str(tmp_path))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    edit(spec)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    with pytest.raises(harness.BenchSpecError, match=message):
        for w in spec["workloads"]:
            harness.resolve(root, w["name"])


@pytest.mark.parametrize("file, edit, message", [
    ("configs/mnist_2nn.tiny.json", lambda c: c.update(dtype="bfloat16"),
     "dtype"),
    ("configs/mnist_2nn.tiny.json",
     lambda c: c.update(matmul_precision="highest"), "matmul_precision"),
    ("configs/mnist_2nn.tiny.json",
     lambda c: c["algorithm"].update(name="sgp"), "algorithm.name"),
    ("traffic/tiny_k2.json", lambda t: t.update(links={"drop": 0.2}),
     "links"),
    ("traffic/tiny_k2.json", lambda t: t["topology"].update(kind="ring"),
     "topology.kind"),
    ("traffic/tiny_k2.json",
     lambda t: t["topology"].update(time_varying=False),
     "topology.time_varying"),
    ("traffic/tiny_k2.json", lambda t: t.update(loop="open"), "loop"),
    ("traffic/tiny_k2.json", lambda t: t.update(superstep_rounds=0),
     "superstep_rounds"),
])
def test_unimplemented_settings_are_refused(tmp_path, file, edit, message):
    """A configuration or mix that asks for what the harness and the
    reference do not implement is refused, not run as something else."""
    root = make_root(str(tmp_path))
    path = os.path.join(root, "bench", file)
    with open(path) as f:
        data = json.load(f)
    edit(data)
    with open(path, "w") as f:
        json.dump(data, f)
    with pytest.raises(harness.BenchSpecError, match=message):
        harness.resolve(root, "mnist_2nn.tiny_k2")


def _run(root, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", workload, "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_measuring_path_exits_nonzero_without_a_tpu():
    p = _run(ROOT, _spec()["workloads"][0]["name"])
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_bench_files_alone_exit_nonzero(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files has no program to run."""
    root = tmp_path / "alone"
    root.mkdir()
    (root / "BENCHMARK.json").write_text(
        open(os.path.join(ROOT, "BENCHMARK.json")).read())
    import shutil

    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(root), _spec()["workloads"][0]["name"])
    assert p.returncode != 0
    assert not p.stdout.strip()
