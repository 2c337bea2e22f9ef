"""Split a traced window by the program's own spans and scopes.

``trace_reduce`` names what the device did (programs, collectives, idle
time). This module names what the host was doing in each idle instant and
which phase of the SI protocol each device operation belongs to, from what
the program itself writes into the trace:

* host spans on the driver's thread (``jax.profiler.TraceAnnotation``):
  the benchmark's ``bench.call.<program>`` around each program call, and
  the driver's ``tpcc.start``, ``tpcc.round``, ``tpcc.draw``,
  ``tpcc.gate``, ``tpcc.tally``, ``tpcc.read`` and ``tpcc.finish``
  (``db/tpcc.run_mixed_rounds``);
* the named scopes ``si.read`` and ``si.commit`` of the protocol
  (``core/si.run_round``, ``core/store.distributed_round``,
  ``core/store.distributed_readonly_round``, ``db/tpcc._snapshot_read``),
  which XLA keeps in each instruction's ``op_name``.

A trace without them (the program before it had them) reduces to empty
tables: everything idle is charged to ``driver`` and nothing is scoped.

    python3 bench/span_reduce.py <trace dir> [--hlo <dir of HLO text>]

prints the stat names of the first op events of each chip, the scope source
found, and the tables below. ``--hlo`` maps (``hlo_module``, ``hlo_op``) to
``op_name`` through compiled HLO text (``compiled.as_text()``, or the
``*after_optimizations.txt`` files of ``--xla_dump_to``) where the op events
carry no ``op_name`` of their own.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import NamedTuple, Optional

import trace_reduce as tr

SPAN = re.compile(r"^(bench\.call\.|tpcc\.)")
SCOPES = ("si.read", "si.commit")
# a scope as one element of an op_name path, bare or inside HLO text
_SCOPE = re.compile(r'(?:^|[/"])(si\.read|si\.commit)(?=[/"]|$)')
_HLO_OP = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = [^\n]*?'
                     r'metadata=\{op_name="([^"]*)"', re.M)
_HLO_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)


class Event(NamedTuple):
    """``trace_reduce.Event`` with the event's stats, which
    ``trace_reduce.reduce`` reads through the same five fields."""
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: dict = {}


def load(trace_dir: str) -> list:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``, with
    its stats."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    return [Event(p.name, ln.name, e.name, float(e.start_ns),
                  float(e.duration_ns), dict(e.stats))
            for p in data.planes for ln in p.lines for e in ln.events]


def hlo_op_names(texts) -> dict:
    """``{(module, instruction): op_name}`` from compiled HLO texts."""
    out = {}
    for text in texts:
        m = _HLO_MODULE.search(text)
        if m is None:
            continue
        for op, name in _HLO_OP.findall(text):
            out[(m.group(1), op)] = name
    return out


class SpanReduction(NamedTuple):
    idle_s: float            # the first chip's idle time in the window
    idle_by_span: dict       # innermost open span -> idle seconds
    idle_gaps: list          # [(span at the gap's start, seconds)], longest
    #                          first
    scoped: dict             # scope -> device seconds, mean over the chips
    scope_source: str        # the op stat that carried op_name, "hlo", or
    #                          "none"
    spans: dict              # span name -> (count, seconds)
    first_op_stats: dict     # chip -> stat names of its first op event
    call_lead_s: Optional[float]  # the most any program's run on the first
    #                          chip started before its ``bench.call`` span
    #                          opened (negative: never before); None where
    #                          runs and calls do not pair up


def _window(events):
    win = [e for e in events if e.plane.startswith("/host:")
           and e.name == tr.WINDOW]
    if win:
        return (min(e.start_ns for e in win),
                max(e.start_ns + e.dur_ns for e in win))
    calls = [e for e in events if e.name in ("tpcc.start", "tpcc.finish")]
    if not calls:
        raise ValueError(f"the trace holds no {tr.WINDOW!r} span and no "
                         "driver call")
    return (min(e.start_ns for e in calls),
            max(e.start_ns + e.dur_ns for e in calls))


def _segments(spans, lo, hi):
    """Cut ``[lo, hi]`` at every span boundary: ``[(start, end, name)]``
    with the innermost span open there, ``driver`` where none is. Spans of
    one thread nest; one that outlives its parent is cut at the parent's
    end."""
    segs = []
    t = lo

    def emit(end, name):
        nonlocal t
        a, b = max(t, lo), min(end, hi)
        if b > a:
            segs.append((a, b, name))
        t = max(t, end)

    stack = []
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            emit(end, top)
        emit(s, stack[-1][1] if stack else tr.DRIVER)
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    while stack:
        end, top = stack.pop()
        emit(end, top)
    emit(hi, tr.DRIVER)
    return segs


def _charge(gaps, segs):
    """Idle seconds by the span open over each part of each gap."""
    out = collections.defaultdict(float)
    starts = [s for s, _, _ in segs]
    for g0, dur in gaps:
        g1 = g0 + dur
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(segs) and segs[i][0] < g1:
            a, b = max(g0, segs[i][0]), min(g1, segs[i][1])
            if b > a:
                out[segs[i][2]] += (b - a) / 1e9
            i += 1
    return dict(out)


def _scope_of(e, hlo) -> tuple:
    """(scope or None, the source that named it or None)."""
    for k, v in e.stats.items():
        if isinstance(v, str):
            m = _SCOPE.search(v)
            if m:
                return m.group(1), k
    if hlo:
        name = hlo.get((e.stats.get("hlo_module"), e.stats.get("hlo_op")))
        if name:
            m = _SCOPE.search(name)
            return (m.group(1) if m else None), "hlo"
    return None, None


def reduce(events, hlo: Optional[dict] = None) -> SpanReduction:
    lo, hi = _window(events)

    def inside(e):
        return e.start_ns >= lo and e.start_ns + e.dur_ns <= hi

    spans = [(e.start_ns, e.start_ns + e.dur_ns, e.name) for e in events
             if e.plane.startswith("/host:") and SPAN.match(e.name)
             and inside(e)]
    table = collections.defaultdict(lambda: [0, 0.0])
    for s, e, name in spans:
        table[name][0] += 1
        table[name][1] += (e - s) / 1e9
    chips = sorted({e.plane for e in events if tr.DEVICE.match(e.plane)},
                   key=lambda p: int(tr.DEVICE.match(p).group(1)))
    scoped = dict.fromkeys(SCOPES, 0.0)
    sources = collections.Counter()
    first_stats = {}
    first_ops = []
    runs = {}
    for i, chip in enumerate(chips):
        ops = [e for e in events if e.plane == chip and e.line == tr.OPS
               and inside(e)]
        if ops:
            first = min(ops, key=lambda e: e.start_ns)
            first_stats[chip] = sorted(first.stats)
        for e in ops:
            scope, src = _scope_of(e, hlo)
            if src:
                sources[src] += 1
            if scope:
                scoped[scope] += e.dur_ns / 1e9
        if i == 0:
            first_ops = [(e.start_ns, e.start_ns + e.dur_ns) for e in ops]
            runs = collections.defaultdict(list)
            for e in events:
                if e.plane == chip and e.line == tr.MODULES and inside(e):
                    runs[tr._program(e.name)].append(e.start_ns)
    n = max(len(chips), 1)
    calls = collections.defaultdict(list)
    for s, _, name in spans:
        if name.startswith("bench.call."):
            prog = name[len("bench.call."):]
            calls[prog if prog == "version_mover" else f"{prog}_round"] \
                .append(s)
    leads = [c - r for k, v in calls.items()
             for c, r in zip(sorted(v), sorted(runs.get(k, ())))]
    paired = all(len(v) == len(runs.get(k, ())) for k, v in calls.items())
    gaps = tr._gaps(first_ops, lo, hi)
    segs = _segments(spans, lo, hi)
    names = [name for _, _, name in segs]
    seg_starts = [s for s, _, _ in segs]
    idle_gaps = sorted(
        ((names[max(bisect.bisect_right(seg_starts, g0) - 1, 0)]
          if segs else tr.DRIVER, dur / 1e9) for g0, dur in gaps),
        key=lambda g: -g[1])
    return SpanReduction(
        idle_s=sum(d for _, d in gaps) / 1e9,
        idle_by_span=_charge(gaps, segs),
        idle_gaps=idle_gaps,
        scoped={k: v / n for k, v in scoped.items()},
        scope_source=sources.most_common(1)[0][0] if sources else "none",
        spans={k: (c, s) for k, (c, s) in table.items()},
        first_op_stats=first_stats,
        call_lead_s=max(leads) / 1e9 if leads and paired else None)


def summary(red: SpanReduction, rounds: int, top: int = 10) -> str:
    """The reduction as text, per round where a number is a time."""
    out = [f"first op events' stats: {red.first_op_stats}",
           f"scope source: {red.scope_source}",
           f"idle {1e3 * red.idle_s / rounds:.3f} ms a round, by the "
           f"innermost host span:"]
    for k, v in sorted(red.idle_by_span.items(), key=lambda kv: -kv[1]):
        out.append(f"  {k:28s} {1e3 * v / rounds:10.3f} ms/round "
                   f"{v / red.idle_s if red.idle_s else 0:8.2%}")
    out.append("scoped device time:")
    for k, v in red.scoped.items():
        out.append(f"  {k:28s} {1e3 * v / rounds:10.3f} ms/round")
    out.append("host spans (count, seconds):")
    for k, (c, s) in sorted(red.spans.items()):
        out.append(f"  {k:28s} {c:8d} {s:12.6f}")
    out.append(f"longest idle gaps: {red.idle_gaps[:top]}")
    out.append(f"a program's run started before its call span by at most "
               f"{red.call_lead_s} s")
    return "\n".join(out)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--hlo", default=None,
                    help="a directory of compiled HLO text files")
    args = ap.parse_args(argv)
    hlo = None
    if args.hlo:
        # XLA's dumps hold each module before and after optimization: only
        # the compiled module's instructions run
        paths = glob.glob(os.path.join(args.hlo, "**", "*.txt"),
                          recursive=True)
        paths = [p for p in paths if p.endswith("after_optimizations.txt")] \
            or paths
        texts = []
        for path in paths:
            with open(path) as f:
                texts.append(f.read())
        hlo = hlo_op_names(texts)
    red = reduce(load(args.trace_dir), hlo)
    rounds = red.spans.get("tpcc.round", (1, 0.0))[0] or 1
    print(f"rounds {rounds}")
    print(summary(red, rounds))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
