"""Reduce a JAX profiler trace to the benchmark's device numbers.

Two stages, kept apart so that the second can be checked on a small
recorded trace (``bench/testdata/trace_small.json``):

1. :func:`load_xplane` reads the ``.xplane.pb`` the profiler wrote into
   plain event tuples ``(plane, line, name, start_ns, end_ns)``: the
   device planes' ``XLA Ops`` and ``XLA Modules`` lines, and every host
   event (host spans of the benchmark are named ``bench.*``).
2. :class:`TraceSummary` reduces those events over the traced window
   (the host span ``bench.window``): device busy time as the union of
   op intervals, device time per program or op name, the top device
   ops, and the idle gaps labelled by what the host was doing.

    python -m bench.tracing <file.xplane.pb>   # planes, lines, top names

Times are seconds unless a name says ``_ns``.
"""
from __future__ import annotations

import collections
import json
import re
import sys
from pathlib import Path

DEVICE_PLANE = "/device:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"

_SUFFIX = re.compile(r"(\.\d+|\(\d+\))+$")


def load_xplane(path) -> list[tuple]:
    """Events of one ``.xplane.pb``: device ops and modules, host events."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                start = int(ev.start_ns)
                out.append((plane.name, line.name, ev.name, start,
                            start + int(ev.duration_ns)))
    return out


def find_xplane(trace_dir) -> Path:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union_ns(intervals) -> list[tuple[int, int]]:
    """Merged, sorted ``(start, end)`` intervals."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def base_name(name: str) -> str:
    """A host event's name without its trailing instance numbers."""
    return _SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """A device op's HLO instruction name (``fusion.153``), without the
    instruction text that the TPU trace appends to it."""
    return name.split(" = ", 1)[0].lstrip("%")


class TraceSummary:
    """The traced window's events, and the numbers read from them."""

    def __init__(self, events):
        self.events = [tuple(e) for e in events]
        host = [e for e in self.events if not e[0].startswith(DEVICE_PLANE)]
        windows = [e for e in host if e[2] == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        _, self.host_line, _, self.t0, self.t1 = windows[0]
        self.host = [e for e in host if e[1] == self.host_line]
        self.devices = sorted({e[0] for e in self.events
                               if e[0].startswith(DEVICE_PLANE)
                               and e[1] == OPS_LINE})

    # -- clipping --------------------------------------------------------
    def _clip(self, s, e):
        return max(s, self.t0), min(e, self.t1)

    def _device(self, line):
        for plane, ln, name, s, e in self.events:
            if ln == line and plane.startswith(DEVICE_PLANE):
                cs, ce = self._clip(s, e)
                if ce > cs:
                    yield plane, name, cs, ce

    # -- numbers ---------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_intervals(self, plane) -> list[tuple[int, int]]:
        return union_ns((s, e) for p, _, s, e in self._device(OPS_LINE)
                        if p == plane)

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices seen."""
        if not self.devices:
            return 0.0
        total = sum(e - s for p in self.devices
                    for s, e in self.busy_intervals(p))
        return total * 1e-9 / len(self.devices)

    def device_s(self, pattern, *, line=MODULES_LINE) -> float:
        """Device seconds of the events on ``line`` whose name matches
        the regular expression ``pattern`` (summed over devices)."""
        rx = re.compile(pattern)
        return 1e-9 * sum(e - s for _, name, s, e in self._device(line)
                          if rx.search(name))

    def top_ops(self, k=10) -> list[list]:
        """The ``k`` ops that took the most device time of their own, as
        ``[name, seconds]``: an op's time less that of the ops nested in
        it (a ``while`` holds its body's ops on the same line)."""
        own = collections.Counter()
        for plane in self.devices:
            ops = sorted(((s, -e, name) for p, name, s, e in
                          self._device(OPS_LINE) if p == plane))
            stack: list[list] = []  # [end, name, own time] of open ops
            for s, neg_e, name in ops:
                while stack and stack[-1][0] <= s:
                    _, n, t = stack.pop()
                    own[n] += t
                if stack:
                    stack[-1][2] -= -neg_e - s
                stack.append([-neg_e, op_name(name), -neg_e - s])
            for _, n, t in stack:
                own[n] += t
        return [[n, t * 1e-9] for n, t in own.most_common(k)]

    def idle_gaps(self) -> list[tuple[int, int]]:
        """Intervals of the window in which no device ran an op."""
        busy = union_ns((s, e) for _, _, s, e in self._device(OPS_LINE))
        gaps, t = [], self.t0
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if self.t1 > t:
            gaps.append((t, self.t1))
        return gaps

    def host_activity(self, times_ns) -> list[str]:
        """For each time (ascending), the innermost host event on the
        benchmark's thread that spans it; ``bench.window`` when nothing
        narrower does.  One sweep over the host events."""
        spans = sorted((s, e, name) for _, _, name, s, e in self.host)
        active: list[tuple] = []  # (start, end, name) open at the sweep
        out, i = [], 0
        for t in times_ns:
            while i < len(spans) and spans[i][0] <= t:
                active.append(spans[i])
                i += 1
            active = [a for a in active if a[1] > t]
            inner = min(active, key=lambda a: a[1] - a[0], default=None)
            out.append(inner[2] if inner else WINDOW_SPAN)
        return out

    def idle_by_host(self, k=10) -> list[list]:
        """Idle device seconds grouped by what the host was doing at each
        gap's midpoint, the ``k`` largest as ``[activity, seconds]``."""
        gaps = self.idle_gaps()
        labels = self.host_activity([(s + e) // 2 for s, e in gaps])
        tot = collections.Counter()
        for (s, e), name in zip(gaps, labels):
            tot[base_name(name)] += e - s
        return [[n, t * 1e-9] for n, t in tot.most_common(k)]


def load_events(path) -> list[tuple]:
    """Events kept as a JSON list of ``[plane, line, name, start_ns,
    end_ns]`` (as ``bench/testdata/trace_small.json``)."""
    return [tuple(e) for e in json.loads(Path(path).read_text())]


def main(argv=None) -> int:
    (path,) = argv if argv is not None else sys.argv[1:]
    events = load_xplane(path)
    lines = collections.Counter((e[0], e[1]) for e in events)
    for (plane, line), n in sorted(lines.items()):
        print(f"{plane} | {line} | {n} events")
    names = collections.Counter()
    for plane, line, name, s, e in events:
        if plane.startswith(DEVICE_PLANE):
            names[(line, op_name(name))] += e - s
    for (line, name), ns in names.most_common(40):
        print(f"{line} | {name} | {ns * 1e-9:.6f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
