"""Per-chip readings of a traced window, for the cells on a mesh.

:meth:`bench.tracing.TraceSummary.device_s` sums a program's or an op's
device time over every device plane.  A count over a mesh ends when its
slowest chip does, so the mesh cell's readers take the largest plane
instead: :func:`slowest_s`.
"""
from __future__ import annotations

import collections
import re

from bench import tracing


def plane_s(trace, pattern, *, line=tracing.MODULES_LINE) -> dict:
    """``{device plane: seconds}`` of the events on ``line``, clipped to
    the window, whose name matches the regular expression ``pattern``:
    the program's name on the modules line, the HLO instruction's name
    (:func:`bench.tracing.op_name`) on the ops line."""
    rx = re.compile(pattern)
    out: collections.Counter = collections.Counter()
    for plane, ln, name, s, e in trace.events:
        if ln != line or not plane.startswith(tracing.DEVICE_PLANE):
            continue
        s, e = max(s, trace.t0), min(e, trace.t1)
        if e > s and rx.search(tracing.op_name(name)
                               if line == tracing.OPS_LINE else name):
            out[plane] += e - s
    return {p: ns * 1e-9 for p, ns in out.items()}


def slowest_s(trace, pattern, *, line=tracing.MODULES_LINE) -> float:
    """The largest plane's seconds of :func:`plane_s`; 0 where none."""
    return max(plane_s(trace, pattern, line=line).values(), default=0.0)
