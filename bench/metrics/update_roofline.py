"""update_roofline (%): the work the fused momentum/descent update
requires (``bench.work.update_work``: read x, v, g and write x', v' of the
unpadded bank, K times a round) over the device time of the
``fused_update_bank`` kernel, against the chip's peaks.  Bound by bytes."""
from bench import devtrace, work

KERNEL = "fused_update_bank"


def read(run):
    total = devtrace.kernel_ns(run.trace, KERNEL)
    if not total or run.rounds <= 0:
        return None
    calls = run.rounds * run.cell.config["algorithm"]["local_steps"]
    flops, nbytes = work.update_work(run.n // run.chips, run.dim,
                                     run.itemsize)
    share, _ = work.roofline(calls * flops, calls * nbytes, total / 1e9,
                             run.peaks)
    return share
