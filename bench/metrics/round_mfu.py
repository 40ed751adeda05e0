"""round_mfu (%): the model FLOPs of the rounds in the traced window over
the window's length times the chips' peak.  The FLOPs are what training
requires (forward and backward of both SAM passes for every example of
every local step, and the in-scan eval's forward), counted from the layer
shapes by ``bench.work.train_flops_per_round``."""
from bench import work


def read(run):
    if run.rounds <= 0 or run.trace.window_ns <= 0:
        return None
    flops = work.train_flops_per_round(run.layers, run.cell.config,
                                       run.cell.traffic) * run.rounds
    seconds = run.trace.window_ns / 1e9
    return 100.0 * flops / (seconds * run.peaks["flops"] * run.chips)
