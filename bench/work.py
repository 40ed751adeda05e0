"""The yardstick: peaks of each device, and the operations and bytes that
the work of a round requires, computed from shapes.

Required work is what the algorithm needs, whatever implements it: a
padded copy, a second pass or an output nobody reads is not required
work.  So a roofline share computed from these functions cannot exceed
100% unless the time leaves out part of the work.
"""
from __future__ import annotations

__all__ = ["PEAKS", "peaks_for", "train_flops_per_round", "update_work",
           "gossip_work", "roofline"]

# Per chip.  Source: Google Cloud documentation, "TPU v5e" (system
# architecture): 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; a device that is not in
    the table is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def train_flops_per_round(layers, config: dict, traffic: dict) -> float:
    """Model FLOPs of one round: every client's K local steps of B
    examples, each a forward and a backward pass per SAM gradient (two
    with ``rho > 0``), plus the in-scan eval's forward over the test set,
    spread over the ``eval_every`` rounds it covers.

    ``layers`` are ``(name, forward multiply-adds per example, needs input
    gradient)``; the backward pass costs one weight-gradient product per
    layer and one input-gradient product per layer that needs it.
    Recomputation does not count."""
    alg, fed = config["algorithm"], config["federation"]
    fwd = sum(2 * m for _, m, _ in layers)
    bwd = sum(2 * m + (2 * m if igrad else 0) for _, m, igrad in layers)
    passes = 2 if alg["rho"] > 0 else 1
    train = (fed["n_clients"] * alg["local_steps"] * alg["batch_size"]
             * passes * (fwd + bwd))
    eval_ = config["dataset"]["n_test"] * fwd / traffic["eval_every"]
    return float(train + eval_)


def update_work(n: int, d: int, itemsize: int = 4):
    """(FLOPs, bytes) one fused momentum/descent update of an (n, d) bank
    requires: v' = alpha v + g and x' = x - eta v' (4 FLOPs an element);
    read x, v, g and write x', v' once (v is float32).  The de-biased z it
    also writes is not read by the solver, and padding is not required."""
    return 4.0 * n * d, float(n * d * (3 * itemsize + 2 * 4))


def gossip_work(n: int, k_max: int, d: int, itemsize: int = 4):
    """(FLOPs, bytes) one push-sum mix of an (n, d) bank requires: a
    multiply-add per neighbour slot and element; read and write the bank
    once.  The same work is required of a dense matmul that implements it."""
    return 2.0 * n * k_max * d, float(2 * n * d * itemsize)


def roofline(flops: float, nbytes: float, seconds: float, peaks: dict):
    """(share of the roofline in %, the bound that sets it): the least time
    the chip could take, the larger of operations over peak FLOP/s and
    bytes over peak bandwidth, over the measured time."""
    t_flops = flops / peaks["flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_flops else "flops"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
