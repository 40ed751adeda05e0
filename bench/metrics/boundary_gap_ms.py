"""boundary_gap_ms (ms): the mean time the device sits idle between two
consecutive executions of the superstep program, the boundary where the
host loop (``FLTrainer.fit`` -> ``RoundProgram.run_superstep``) fetches the
history and dispatches the next superstep.  What the host was doing in
the longest gaps is in the run's breakdown."""
from bench import devtrace


def read(run):
    t = run.trace
    idle = []
    for d in t.devices:
        runs = devtrace.module_runs(t, d)
        for (_, end, _), (start, _, _) in zip(runs, runs[1:]):
            busy = sum(b - a for a, b in devtrace.union(t.ops.get(d, ()),
                                                        end, start))
            idle.append(max(start - end, 0.0) - busy)
    if not idle:
        return None
    return sum(idle) / len(idle) / 1e6
