"""``correct`` comes out false when the timed path is broken underneath, and
for the control, at a size a test run holds (CPU, interpreted kernels).

The run below skips only the harness's look for a chip; everything else
is a whole run of a CPU-sized cell held to the real cell's limits."""
from __future__ import annotations

import json
import os

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
