"""Reduction of a profiler trace to what the per-layer metrics read.

A trace is read once into a small, JSON-able :class:`Trace`: per device,
the operations that ran (start, end, name) and the executions of compiled
programs; on the host, the benchmark's own spans (``bench.*``) and the
other host events.  The readers in ``bench/metrics`` take their numbers
from it, and the tests check it on a recorded trace.

What a TPU v5e trace holds (JAX 0.9): a plane ``/device:TPU:<i>`` per chip
with the lines ``XLA Modules`` (one event per execution of a compiled
program, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one event per
executed HLO instruction, named by its HLO text ``%name = ...``; a
``while`` loop's event spans the events of its body, so events nest) and
``Async XLA Ops`` (DMA copies that overlap compute, left out here); and
a plane ``/host:CPU`` whose lines are host threads.  A Pallas kernel is a
``custom-call`` with ``custom_call_target="tpu_custom_call"``, named after
the kernel.  Device and host events share one clock, in nanoseconds, to
within about a millisecond: a program's execution on the device can read
as starting up to ~1 ms before the host dispatched it.  The window is the
host's (the benchmark's ``bench.superstep`` spans); device time is clipped
to it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

__all__ = ["Trace", "from_profile", "from_dict", "to_dict", "union",
           "busy_ns", "leaves", "op_name", "kernel_name", "is_kernel",
           "kernel_ns", "idle_gaps", "module_runs", "op_totals"]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
SUPERSTEP_SPAN = "bench.superstep"
_SUFFIX = re.compile(r"(\.\d+)+$")
_HLO_NAME = re.compile(r"^%?([^\s=]+)\s*=")
_MOSAIC = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Trace:
    # device index -> [(start, end, name)], each sorted by start; ops are
    # the leaf operations (no event nested inside them)
    ops: dict
    modules: dict
    # host: the benchmark's spans and the other host events
    spans: list
    host: list
    window: tuple  # (start, end) of the traced window
    kernels: list = dataclasses.field(default_factory=list)  # Pallas ops

    @property
    def devices(self):
        return sorted(self.ops)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


def kernel_name(name: str) -> str:
    """An operation's name without XLA's numeric suffixes: every Pallas
    call of the program is named after its kernel (``fused_update_bank``,
    ``gossip_gather``, ``gossip_matmul``)."""
    return _SUFFIX.sub("", name)


def op_name(hlo_text: str) -> str:
    """``fused_update_bank.7`` from ``%fused_update_bank.7 = (...) ...``."""
    m = _HLO_NAME.match(hlo_text)
    return m.group(1) if m else hlo_text


def leaves(events):
    """The events that contain no other event of the same line: the
    operations themselves, not the loops and calls around them.  Events of
    one line nest properly, so an event holds another exactly when the next
    one in order of start begins before it ends."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt[0] >= e[1]]


def _events(line):
    return [(float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def from_profile(trace_dir: str, n_devices: int) -> Trace:
    """Read the ``.xplane.pb`` under ``trace_dir`` (the newest one)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, modules, spans, host, kernels = {}, {}, [], [], set()
    dev = re.compile(r"^/device:[A-Z]+:(\d+)$")
    for plane in data.planes:
        m = dev.match(plane.name)
        if m and int(m.group(1)) < n_devices:
            d = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs = _events(line)
                    kernels.update(op_name(n) for _, _, n in evs
                                   if _MOSAIC in n)
                    ops[d] = [(a, b, op_name(n)) for a, b, n in leaves(evs)]
                elif line.name == MODULES_LINE:
                    modules[d] = sorted(_events(line))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in _events(line):
                    (spans if ev[2].startswith(SPAN_PREFIX)
                     else host).append(ev)
    spans.sort()
    host.sort()
    steps = [s for s in spans if s[2] == SUPERSTEP_SPAN]
    if not steps:
        raise ValueError("the trace holds no bench.superstep span")
    window = (steps[0][0], steps[-1][1])
    return Trace(ops, modules, spans, host, window, sorted(kernels))


def to_dict(t: Trace) -> dict:
    return {"ops": {str(k): v for k, v in t.ops.items()},
            "modules": {str(k): v for k, v in t.modules.items()},
            "spans": t.spans, "host": t.host, "window": list(t.window),
            "kernels": list(t.kernels)}


def from_dict(d: dict) -> Trace:
    def evs(x):
        return sorted((float(a), float(b), str(c)) for a, b, c in x)

    return Trace({int(k): evs(v) for k, v in d["ops"].items()},
                 {int(k): evs(v) for k, v in d["modules"].items()},
                 evs(d["spans"]), evs(d["host"]), tuple(d["window"]),
                 list(d.get("kernels", [])))


def union(intervals, lo=None, hi=None):
    """Merge (start, end, ...) intervals, clipped to [lo, hi]."""
    out = []
    for iv in sorted(intervals):
        a, b = iv[0], iv[1]
        if lo is not None:
            a = max(a, lo)
        if hi is not None:
            b = min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(t: Trace, d: int) -> float:
    """Time in the window in which some operation ran on device ``d``."""
    return sum(b - a for a, b in union(t.ops.get(d, ()), *t.window))


def op_totals(t: Trace, d: int) -> dict:
    """Device time per operation name within the window (an operation
    that straddles an end of the window counts with its part inside)."""
    lo, hi = t.window
    tot = {}
    for a, b, name in t.ops.get(d, ()):
        if b > lo and a < hi:
            tot[name] = tot.get(name, 0.0) + (min(b, hi) - max(a, lo))
    return tot


def is_kernel(t: Trace, name: str) -> bool:
    """Is this operation one of the program's Pallas kernels?"""
    return name in t.kernels


def kernel_ns(t: Trace, *names):
    """Device time of the Pallas kernels named ``names`` (without XLA's
    suffixes) in the window, averaged over the devices; None where the
    trace holds none of them."""
    found, total = False, 0.0
    for d in t.devices:
        for name, ns in op_totals(t, d).items():
            if is_kernel(t, name) and kernel_name(name) in names:
                found, total = True, total + ns
    return total / len(t.devices) if found else None


def module_runs(t: Trace, d: int):
    """Executions of the program that took most device time in the window
    (the superstep), as (start, end, name), in order; an execution belongs
    to the window when its midpoint does."""
    lo, hi = t.window
    runs = [m for m in t.modules.get(d, ()) if lo <= (m[0] + m[1]) / 2 <= hi]
    if not runs:
        return []
    total = {}
    for a, b, name in runs:
        total[name] = total.get(name, 0.0) + (b - a)
    top = max(total, key=total.get)
    return [m for m in runs if m[2] == top]


def idle_gaps(t: Trace, d: int, top: int | None = None):
    """The longest idle stretches of device ``d`` in the window, longest
    first, each as (start, end, what it was waiting for).  A gap inside an
    execution of a compiled program is named by the program and the
    operations on either side; a gap between executions by the innermost
    host event, the benchmark's spans included, over its midpoint."""
    ops = t.ops.get(d, ())
    busy = union(ops, *t.window)
    edges = [t.window[0]] + [x for iv in busy for x in iv] + [t.window[1]]
    gaps = sorted(((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]),
                  key=lambda g: g[0] - g[1])[:top]
    starts = [o[0] for o in ops]
    host = t.spans + t.host
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        inside = [m for m in t.modules.get(d, ()) if m[0] <= mid <= m[1]]
        if inside:
            i = bisect.bisect_left(starts, b)
            before = ops[i - 1][2] if i > 0 else "start"
            after = ops[i][2] if i < len(ops) else "end"
            name = f"in {inside[0][2].split('(')[0]}: {before} -> {after}"
        else:
            under = [h for h in host if h[0] <= mid <= h[1]]
            name = ("host: " + min(under, key=lambda h: h[1] - h[0])[2]
                    if under else "host: no event")
        out.append((a, b, name))
    return out
