"""A small benchmark tree for the tests: a copy of ``bench/`` beside the
program's ``src/``, with a ``BENCHMARK.json`` that names a CPU-sized cell."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY_CONFIG = {
    "name": "mnist_2nn.tiny",
    "source": "https://arxiv.org/abs/2310.05093",
    "model": "mnist_2nn",
    "dtype": "float32",
    "dataset": {"name": "mnist", "seed": 0, "shape": [784], "n_classes": 10,
                "margin": 4.0, "n_train": 640, "n_test": 100},
    "federation": {"n_clients": 8, "dirichlet_alpha": 0.3,
                   "samples_per_client": 64},
    "algorithm": {"name": "dfedsgpsm", "local_steps": 2, "batch_size": 8,
                  "rho": 0.1, "momentum": 0.9, "lr": 0.1, "lr_decay": 0.998},
    "assumed": {}, "reduced": ["n_clients"],
}

TINY_TRAFFIC = {
    "name": "tiny_k2",
    "topology": {"kind": "kout", "k_out": 2, "time_varying": True},
    "links": "perfect", "gossip": "sparse", "superstep_rounds": 3,
    "eval_every": 3, "loop": "closed",
}


def make_root(tmp, limits=None):
    """A checkout-like tree under ``tmp`` holding one tiny cell,
    ``mnist_2nn.tiny_k2``; returns its root."""
    root = os.path.join(tmp, "root")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "mnist_2nn.tiny.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    with open(os.path.join(b, "traffic", "tiny_k2.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    with open(os.path.join(b, "limits", "mnist_2nn.tiny_k2.json"), "w") as f:
        json.dump({"limits": limits or {}}, f)
    spec["configs"].append({
        "name": "mnist_2nn.tiny", "source": TINY_CONFIG["source"],
        "file": "bench/configs/mnist_2nn.tiny.json",
        "reduced": ["n_clients"], "why": "CPU-sized test cell"})
    spec["workloads"].append({
        "name": "mnist_2nn.tiny_k2", "config": "mnist_2nn.tiny",
        "traffic": "tiny_k2", "chips": 1, "why": "CPU-sized test cell"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root
