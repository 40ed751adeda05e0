"""update_ms (ms/round): device time of the ``fused_update_bank`` kernel
per round, averaged over the chips."""
from bench import devtrace

KERNEL = "fused_update_bank"


def read(run):
    total = devtrace.kernel_ns(run.trace, KERNEL)
    if total is None or run.rounds <= 0:
        return None
    return total / run.rounds / 1e6
