"""Resolve one benchmark cell from ``BENCHMARK.json`` by name.

Everything that belongs to one configuration, one traffic mix, one cell's
limits or one per-layer metric sits in a file of its own, found by name:

  configs   ``BENCHMARK.json`` ``configs[].file`` (a JSON file of sizes)
  models    ``bench/models/<config["model"]>.py`` (the plain reference)
  traffic   ``bench/traffic/<traffic>.json``
  limits    ``bench/limits/<workload>.json`` (the limits of ``correct``)
  metrics   ``bench/metrics/<metric>.py`` (a reader with ``read(run)``)

A later change adds a cell, a mix or a metric by adding such files and
entries; nothing here names a cell.  This module imports neither JAX nor
the program, so it can validate a tree on any machine.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

__all__ = ["Cell", "BenchSpecError", "load_benchmark", "resolve",
           "load_module"]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
END_TO_END_SOURCES = {"host_clock", "device_trace"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# What ``run.build`` and ``reference.py`` implement: (file, key path, the
# only value accepted, the value where the key is absent).  A file that asks
# for anything else is refused, not run as something it did not ask for.
IMPLEMENTED = (
    ("config", ("dtype",), "float32", "float32"),
    ("config", ("matmul_precision",), "default", "default"),
    ("config", ("algorithm", "name"), "dfedsgpsm", None),
    ("traffic", ("topology", "kind"), "kout", None),
    ("traffic", ("topology", "time_varying"), True, None),
    ("traffic", ("links",), "perfect", "perfect"),
    ("traffic", ("loop",), "closed", "closed"),
)


class BenchSpecError(ValueError):
    """A benchmark entry or one of its files is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    """One resolved workload: its configuration, traffic mix, limits and
    the metrics it reports (end-to-end, and per-layer with readers)."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple
    per_layer: tuple  # (metric entry, reader module) pairs
    model_path: str


def _check(ok, msg):
    if not ok:
        raise BenchSpecError(msg)


def _read_json(path, what):
    _check(os.path.isfile(path), f"{what}: no file {path}")
    with open(path) as f:
        try:
            return json.load(f)
        except json.JSONDecodeError as e:
            raise BenchSpecError(f"{what}: {path} is not JSON: {e}") from None


def load_benchmark(root: str) -> dict:
    """``BENCHMARK.json`` at ``root``, with its names checked."""
    spec = _read_json(os.path.join(root, "BENCHMARK.json"), "BENCHMARK.json")
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        _check(isinstance(spec.get(section), list) and spec[section],
               f"BENCHMARK.json: {section!r} must be a non-empty list")
        names = [e.get("name") for e in spec[section]]
        for n in names:
            _check(isinstance(n, str) and NAME.match(n),
                   f"BENCHMARK.json: bad {section} name {n!r}")
        _check(len(set(names)) == len(names),
               f"BENCHMARK.json: duplicate name in {section}")
    for m in spec["end_to_end"]:
        _check(m.get("source") in END_TO_END_SOURCES,
               f"end-to-end metric {m['name']}: source {m.get('source')!r}")
    for m in spec["per_layer"]:
        _check(m.get("source") in SOURCES,
               f"per-layer metric {m['name']}: source {m.get('source')!r}")
    return spec


def load_module(path: str, name: str):
    """Import one Python file of the benchmark by path."""
    _check(os.path.isfile(path), f"no file {path}")
    mod_name = "bench_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _check_implemented(files: dict):
    for what, path, value, default in IMPLEMENTED:
        got = files[what]
        for key in path:
            got = got.get(key, default) if isinstance(got, dict) else default
        _check(got == value, f"{files[what].get('name')}: "
                             f"{'.'.join(path)} {got!r} is not implemented "
                             f"(only {value!r})")
    for key in ("superstep_rounds", "eval_every"):
        got = files["traffic"].get(key)
        _check(isinstance(got, int) and got >= 1,
               f"{files['traffic'].get('name')}: {key} must be an integer "
               f">= 1, not {got!r}")


def _reports(metric: dict, workload: str, end_to_end_names) -> bool:
    listed = metric.get("workloads")
    if listed is not None:
        return workload in listed
    return metric.get("moves", metric["name"]) in end_to_end_names


def resolve(root: str, workload: str) -> Cell:
    """Resolve and validate the cell named ``workload`` of the tree at
    ``root`` (its ``BENCHMARK.json`` and ``bench/``)."""
    bench_dir = os.path.join(root, "bench")
    spec = load_benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    _check(workload in cells, f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    _check(w.get("chips") in (1, 4), f"{workload}: chips must be 1 or 4")
    configs = {c["name"]: c for c in spec["configs"]}
    _check(w.get("config") in configs,
           f"{workload}: unknown config {w.get('config')!r}")
    centry = configs[w["config"]]
    config = _read_json(os.path.join(root, centry["file"]),
                        f"config {centry['name']}")
    _check(config.get("name") == centry["name"],
           f"config file {centry['file']} names {config.get('name')!r}")
    model_path = os.path.join(bench_dir, "models", f"{config['model']}.py")
    _check(os.path.isfile(model_path),
           f"config {centry['name']}: no reference model {model_path}")
    _check(isinstance(w.get("traffic"), str) and NAME.match(w["traffic"]),
           f"{workload}: bad traffic name")
    traffic = _read_json(
        os.path.join(bench_dir, "traffic", f"{w['traffic']}.json"),
        f"traffic {w['traffic']}")
    _check_implemented({"config": config, "traffic": traffic})
    limits = _read_json(
        os.path.join(bench_dir, "limits", f"{workload}.json"),
        f"limits of {workload}")
    for k, v in limits.get("limits", {}).items():
        _check(isinstance(v, (int, float)) and v >= 0,
               f"{workload}: limit {k} must be a number >= 0")

    e2e = tuple(m for m in spec["end_to_end"]
                if m.get("workloads") is None or workload in m["workloads"])
    names = {m["name"] for m in e2e}
    _check("setup_s" in names, f"{workload}: reports no setup_s")
    _check(len(names) >= 2, f"{workload}: needs an end-to-end metric "
                            "besides setup_s")
    per_layer = []
    for m in spec["per_layer"]:
        if not _reports(m, workload, names):
            continue
        _check(m.get("moves") in names,
               f"per-layer {m['name']}: moves {m.get('moves')!r}, which "
               f"{workload} does not report")
        reader = load_module(
            os.path.join(bench_dir, "metrics", f"{m['name']}.py"), m["name"])
        _check(callable(getattr(reader, "read", None)),
               f"metric reader {m['name']}.py has no read(run)")
        per_layer.append((m, reader))
    _check(per_layer, f"{workload}: reports no per-layer metric")
    return Cell(
        name=workload, chips=w["chips"], config=config, traffic=traffic,
        limits=limits, end_to_end=e2e, per_layer=tuple(per_layer),
        model_path=model_path,
    )
