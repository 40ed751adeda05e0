"""The program's named phases in a device trace: its five ``jax.named_scope``s
on the device and ``FLTrainer.fit``'s ``fl.*`` spans on the host.

Where a v5e trace keeps an operation's JAX op name path (its name stack,
such as ``jit(<lambda>)/while/body/closed_call/while/body/closed_call/
sam_grad/vmap(jvp())/div``): neither in the operation's HLO text nor in its
event's own stats (``device_offset_ps``, ``device_duration_ps``), but in the
``tf_op`` stat of the event's metadata, as ``<path>:<op type>`` with the
type empty.  ``jax.profiler.ProfileData`` does not show metadata stats, so
:func:`op_paths` reads them from the ``.xplane.pb`` itself.  An operation
the compiler made without metadata (the in-place writes of a
``concatenate`` it split, async copies, layout copies) has there the path
of the loop or branch it runs in.

The reduction works on a :class:`bench.devtrace.Trace` and a separate map of
operation name to path, so that ``devtrace`` itself is unchanged: the
benchmark's per-layer metrics do not read these yet.  With the paths, the
device time of every operation that is not a Pallas kernel falls in one of
``SCOPES`` or in none, so the six buckets sum to ``xla_ops_ms``.
"""
from __future__ import annotations

import functools
import re

from bench import devtrace

__all__ = ["SCOPES", "op_paths", "scopes_of", "op_scopes", "scope_ns",
           "span_mean_ns"]

# One phase of the round each; none nests inside another.
SCOPES = ("sam_grad", "grad_ravel", "update_pad", "mix", "eval")
_WRAPPERS = re.compile(r"[();]")
# The last component of the path the trace gives an operation that has no
# metadata of its own: the loop or branch it runs in, or nothing.
_CALLERS = ("while", "cond", "")


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """The (field number, value) pairs of one protobuf message: varints as
    ints, length-delimited values as memoryviews, fixed-width ones as
    bytes."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def op_paths(xplane_path: str, device_plane) -> dict:
    """Operation name -> JAX op name path, from the ``tf_op`` stat of each
    operation's event metadata on the planes whose name ``device_plane``
    accepts.  The ``XSpace`` proto: planes are field 1; a plane's name 2,
    event metadata 4 and stat metadata 5 (map entries: key 1, value 2); an
    event metadata's name 2 and stats 5; a stat's metadata id 1 and string
    value 5."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = _text(value)
            elif field == 4:
                events.append(value)
            elif field == 5:
                meta = dict(_fields(dict(_fields(value)).get(2, b"")))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        if not device_plane(name):
            continue
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        for entry in events:
            op, path = "", ""
            for field, meta in _fields(entry):
                if field != 2:
                    continue
                for key, value in _fields(meta):
                    if key == 2:
                        op = devtrace.op_name(_text(value))
                    elif key == 5:
                        stat = dict(_fields(value))
                        if stat.get(1) in tf_op and 5 in stat:
                            path = _text(stat[5])
            path = path.rpartition(":")[0] or path
            if op and path:
                out[op] = path
    return out


@functools.lru_cache(maxsize=None)
def scopes_of(path: str) -> frozenset:
    """The program's scopes among the components of an op name path, with
    transform wrappers peeled: ``vmap(transpose(jvp()))`` is read as
    ``vmap``, ``transpose`` and ``jvp``."""
    return frozenset(w for comp in path.split("/")
                     for w in _WRAPPERS.split(comp)) & frozenset(SCOPES)


def op_scopes(t: devtrace.Trace, paths: dict, d: int):
    """Device ``d``'s leaf operations as (start, end, name, scopes), in
    order.  An operation without a path of its own (see the module's
    docstring) takes the scopes of the next operation that has one: the
    compiler gives an instruction it splits the metadata of the part that
    runs last, such as the final write of a split concatenate."""
    out, scopes = [], frozenset()
    for a, b, name in reversed(t.ops.get(d, ())):
        path = paths.get(name, "")
        if path.rpartition("/")[2] not in _CALLERS:
            scopes = scopes_of(path)
        out.append((a, b, name, scopes))
    return out[::-1]


def scope_ns(t: devtrace.Trace, paths: dict, name):
    """Device time in the window (clipped as ``devtrace.op_totals`` clips)
    of the operations that are not Pallas kernels and lie under the scope
    ``name``, or under none of ``SCOPES`` where ``name`` is None, averaged
    over the devices; None where no such operation in the window carries
    any of ``SCOPES`` (a trace of a program without them)."""
    lo, hi = t.window
    found, total = False, 0.0
    for d in t.devices:
        for a, b, op, scopes in op_scopes(t, paths, d):
            if b <= lo or a >= hi or devtrace.is_kernel(t, op):
                continue
            found = found or bool(scopes)
            if (name in scopes) if name is not None else not scopes:
                total += min(b, hi) - max(a, lo)
    return total / len(t.devices) if found else None


def span_mean_ns(t: devtrace.Trace, name: str):
    """The mean duration of the host events named ``name`` (such as
    ``fl.dispatch``) whose midpoint lies in the window; None where there is
    none."""
    lo, hi = t.window
    durs = [b - a for a, b, n in t.host
            if n == name and lo <= (a + b) / 2 <= hi]
    return sum(durs) / len(durs) if durs else None
