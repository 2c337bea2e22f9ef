"""Reduce a profiler trace to the numbers the per-layer metrics read.

A trace is read once into plain events ``(plane, line, name, start_ns,
dur_ns)`` (``load``), so the reduction (``reduce``) is a function of those
alone and can be tested on events written by hand.

Conventions of a TPU trace, as JAX's profiler writes it:

* each chip is a plane named ``/device:TPU:<n>``; on it the line
  ``XLA Modules`` has one event per program run and ``XLA Ops`` one per
  operation;
* host threads are planes whose name starts with ``/host:``; the
  benchmark's own spans (``jax.profiler.TraceAnnotation``) are events
  there. The span ``bench.window`` bounds the measured window.

``reduce`` returns, over the window and averaged over the chips: the time
in which some operation ran (busy: the union of the op intervals), time and
runs per program, time in collective operations, and the idle gaps on the
first chip, each named by the program call the host was in when the gap
began (``bench.call.<program>``), or ``driver`` when it was in the driver's
own code between calls.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import NamedTuple

MODULES, OPS = "XLA Modules", "XLA Ops"
WINDOW = "bench.window"
DRIVER = "driver"   # the host in the driver, between program calls
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|psum")
DEVICE = re.compile(r"^/device:TPU:(\d+)$")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def load(trace_dir: str) -> list:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    return [Event(p.name, ln.name, e.name, float(e.start_ns),
                  float(e.duration_ns))
            for p in data.planes for ln in p.lines for e in ln.events]


def _union(intervals):
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _gaps(intervals, lo, hi):
    gaps, end = [], lo
    for s, e in sorted(intervals):
        if s > end:
            gaps.append((end, s - end))
        end = max(end, e)
    if hi > end:
        gaps.append((end, hi - end))
    return gaps


def _program(name: str) -> str:
    """A program's stable name: JAX's ``jit_<fn>(<id>)`` cut to ``<fn>``."""
    name = re.sub(r"\(\d+\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


class Reduction(NamedTuple):
    window_s: float
    busy_s: float                 # mean over the chips
    chips: int
    programs: dict                # program -> (runs per chip, seconds per
    #                               chip), both means over the chips
    runs: list                    # program runs on each chip
    collective_s: float           # mean over the chips
    idle_gaps: list               # [(span name, seconds)], longest first


def reduce(events) -> Reduction:
    win = [e for e in events if e.plane.startswith("/host:")
           and e.name == WINDOW]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    lo = min(e.start_ns for e in win)
    hi = max(e.start_ns + e.dur_ns for e in win)

    def inside(e):
        return e.start_ns >= lo and e.start_ns + e.dur_ns <= hi

    chips = sorted({e.plane for e in events if DEVICE.match(e.plane)})
    if not chips:
        raise ValueError("the trace holds no TPU device plane")
    busy = coll = 0.0
    programs = {}
    runs = []
    first_ops = []
    for i, chip in enumerate(chips):
        ops = [(e.start_ns, e.start_ns + e.dur_ns) for e in events
               if e.plane == chip and e.line == OPS and inside(e)]
        busy += _union(ops)
        coll += sum(e.dur_ns for e in events if e.plane == chip
                    and e.line == OPS and inside(e)
                    and COLLECTIVE.search(e.name))
        runs.append(0)
        for e in events:
            if e.plane == chip and e.line == MODULES and inside(e):
                n, s = programs.get(_program(e.name), (0, 0.0))
                programs[_program(e.name)] = (n + 1, s + e.dur_ns)
                runs[-1] += 1
        if i == 0:
            first_ops = ops
    n = len(chips)
    calls = sorted((e.start_ns, e.start_ns + e.dur_ns, e.name)
                   for e in events if e.plane.startswith("/host:")
                   and e.name.startswith("bench.call.") and inside(e))
    starts = [c[0] for c in calls]
    gaps = []
    for start, dur in _gaps(first_ops, lo, hi):
        i = bisect.bisect_right(starts, start) - 1
        name = calls[i][2] if i >= 0 and start < calls[i][1] else DRIVER
        gaps.append((name, dur / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Reduction(
        window_s=(hi - lo) / 1e9, busy_s=busy / n / 1e9, chips=n,
        programs={k: (c / n, s / n / 1e9) for k, (c, s) in programs.items()},
        runs=runs, collective_s=coll / n / 1e9, idle_gaps=gaps)


def breakdown(red: Reduction, top: int = 10) -> dict:
    """The programs that took most device time and the longest idle gaps,
    for the result line."""
    progs = sorted(red.programs.items(), key=lambda kv: -kv[1][1])[:top]
    return {"device_ops": [[k, v[1]] for k, v in progs],
            "idle_gaps": [[n, s] for n, s in red.idle_gaps[:top]]}


def summary(events) -> str:
    """Planes, lines, event counts and the commonest names: what to look at
    before trusting the conventions above on a new chip or JAX."""
    import collections
    lines = collections.Counter((e.plane, e.line) for e in events)
    out = []
    for (plane, line), n in sorted(lines.items()):
        names = collections.Counter(e.name for e in events
                                    if e.plane == plane and e.line == line)
        out.append(f"{plane} | {line} | {n} events | "
                   + ", ".join(f"{k} x{v}" for k, v in names.most_common(8)))
    ops = collections.Counter()
    for e in events:
        if e.line == OPS and DEVICE.match(e.plane):
            name, _, rest = e.name.partition(" = ")
            kind = re.search(r" ([a-z][a-z0-9_-]*)\(", " " + rest)
            ops[f"{name} {kind.group(1) if kind else ''}".strip()] \
                += e.dur_ns
    out.append("ops by device time: " + ", ".join(
        f"{k} {v / 1e9:.6f} s" for k, v in ops.most_common(15)))
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    evs = load(sys.argv[1])
    print(summary(evs))
    red = reduce(evs)
    print(red._replace(idle_gaps=red.idle_gaps[:10]))
