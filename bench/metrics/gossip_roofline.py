"""gossip_roofline (%): the work one push-sum mix of the bank requires
(``bench.work.gossip_work``: 2 n k_max D FLOPs, reading and writing the
bank once), whatever kernel implements it, over that kernel's device time,
against the chip's peaks.  Bound by bytes."""
from bench import devtrace, work

KERNELS = ("gossip_gather", "gossip_matmul")


def read(run):
    total = devtrace.kernel_ns(run.trace, *KERNELS)
    if not total or run.rounds <= 0:
        return None
    flops, nbytes = work.gossip_work(run.n // run.chips, run.k_max, run.dim,
                                     run.itemsize)
    share, _ = work.roofline(run.rounds * flops, run.rounds * nbytes,
                             total / 1e9, run.peaks)
    return share
