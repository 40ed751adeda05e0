"""xla_ops_ms (ms/round): device time of every operation that is not one
of the program's Pallas kernels, per round, averaged over the chips: the
local solver's vmapped SAM gradients, the fused update's pad and slice
copies, the eval and the rest of the XLA glue."""
from bench import devtrace


def read(run):
    t = run.trace
    if run.rounds <= 0 or not t.devices:
        return None
    total = 0.0
    for d in t.devices:
        total += sum(ns for name, ns in devtrace.op_totals(t, d).items()
                     if not devtrace.is_kernel(t, name))
    return total / len(t.devices) / run.rounds / 1e6
