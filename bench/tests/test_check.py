"""``correct`` comes out false when the timed path is broken underneath, and
for the control, at a size a test run holds (CPU, interpreted kernels).

The run below skips only the harness's look for a chip; everything else
is a whole run of a CPU-sized cell held to the real cell's limits."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import calibrate, compare, harness, run
from bench.tests.helpers import BENCH, make_root

CELL = "cifar10_cnn.dfedsgpsm_k10"


def _limits():
    with open(os.path.join(BENCH, "limits", f"{CELL}.json")) as f:
        return json.load(f)["limits"]


def _run(tmp_path, capsys, seed=3000000021):
    root = make_root(str(tmp_path), limits=_limits())
    rc = run.main(["--workload", "mnist_2nn.tiny_k2", "--seed", str(seed),
                   "--seconds", "0.5", "--trace", "0"], root=root,
                  require_tpu=False)
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    return result


def test_sound_program_is_correct(tmp_path, capsys):
    result = _run(tmp_path, capsys)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["checks"]) == set(_limits()) | {"failed_rounds"}


def test_state_left_unchanged_is_caught(tmp_path, capsys, monkeypatch):
    from repro.core import program

    def frozen(self, state, data=None):
        return state, {"loss": state.losses.mean(),
                       "acc": state.losses.mean()}

    monkeypatch.setattr(program.RoundProgram, "step", frozen)
    result = _run(tmp_path, capsys)
    assert result["correct"] is False
    assert result["checks"]["change"]["value"] == pytest.approx(1.0)


def test_half_the_batch_is_caught(tmp_path, capsys, monkeypatch):
    from repro.core import stages

    whole = stages._sample_batch

    def half(data, key, batch_size):
        return {k: v[: batch_size // 2]
                for k, v in whole(data, key, batch_size).items()}

    monkeypatch.setattr(stages, "_sample_batch", half)
    result = _run(tmp_path, capsys)
    assert result["correct"] is False


def test_control_is_caught(tmp_path):
    """The reference in bfloat16, put in the program's place."""
    root = make_root(str(tmp_path), limits=_limits())
    devices = run.start_jax(root)
    cell = harness.resolve(root, "mnist_2nn.tiny_k2")
    recs = calibrate.readings(cell, 3000000023, devices, controls=("control",))
    control = next(r for r in recs if r["who"] == "control")
    program = next(r for r in recs if r["who"] == "program")
    _, ok = compare.judge(control, _limits())
    assert not ok
    _, ok = compare.judge(program, _limits())
    assert ok


def test_four_chip_run_is_correct(tmp_path):
    """A whole run of the tiny cell with ``chips: 4``, on four virtual CPU
    devices: the program's bank sharded over them, the reference on the
    first."""
    root = make_root(str(tmp_path), limits=_limits())
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        if w["name"] == "mnist_2nn.tiny_k2":
            w["chips"] = 4
    with open(path, "w") as f:
        json.dump(spec, f)
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root,
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count=4")
    code = ("import sys; from bench import run; "
            "sys.exit(run.main(sys.argv[1:], require_tpu=False))")
    p = subprocess.run(
        [sys.executable, "-c", code, "--workload", "mnist_2nn.tiny_k2",
         "--seed", "3000000027", "--seconds", "0.5", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
