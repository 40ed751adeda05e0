"""gossip_ms (ms/round): device time of the push-sum mix kernel
(``gossip_gather``, or ``gossip_matmul`` on the dense path) per round,
averaged over the chips."""
from bench import devtrace

KERNELS = ("gossip_gather", "gossip_matmul")


def read(run):
    total = devtrace.kernel_ns(run.trace, *KERNELS)
    if total is None or run.rounds <= 0:
        return None
    return total / run.rounds / 1e6
