"""idle_frac (%): the share of the traced window in which no operation ran
on the device, 1 - union of the device's operation intervals / window,
averaged over the chips used."""
from bench import devtrace


def read(run):
    t = run.trace
    if not t.devices or t.window_ns <= 0:
        return None
    busy = sum(devtrace.busy_ns(t, d) for d in t.devices) / len(t.devices)
    return 100.0 * (1.0 - busy / t.window_ns)
