"""The program's own spans and scopes in a trace: the split of idle time by
the innermost host span and of device time by protocol scope, on a trace
written here; and the driver's spans and read counter in a real CPU trace
of the tiny cell."""
import collections
import tempfile
import types

import jax
import pytest

import adapter
import run
import span_reduce as sr
import spec
import trace_reduce as tr
from conftest import TINY
from repro.db import tpcc

E = sr.Event
HOST = "/host:CPU"
DEV = "/device:TPU:0"
PATH = "jit(neworder_round)/jit(main)/"


def _op(chip, name, start, dur, scope=None, key="tf_op"):
    stats = {"hlo_module": "jit_neworder_round", "hlo_op": name}
    if scope:
        stats[key] = PATH + scope + "/scatter"
    return E(f"/device:TPU:{chip}", tr.OPS, name, start, dur, stats)


def _trace(spans=True, scopes=True):
    """Window 1000-2000. Host: a neworder call 1060-1300 inside round 0
    (1000-1900) after a draw 1000-1050, a gate 1300-1400 holding a read
    1320-1400, a tally 1400-1700, the mover's call 1700-1800, and the
    finish 1900-1980. Each chip runs the program's ops 1100-1300 (read
    scope, then commit) and the mover 1750-1850; chip 0 also an op-by-op
    sum 1600-1620."""
    ev = [E(HOST, "python", tr.WINDOW, 1000, 1000),
          E(HOST, "python", "bench.call.neworder", 1060, 240),
          E(HOST, "python", "bench.call.version_mover", 1700, 100),
          E(HOST, "python", "outside", 0, 10)]
    if spans:
        ev += [E(HOST, "python", "tpcc.round", 1000, 900, {"round": 0}),
               E(HOST, "python", "tpcc.draw", 1000, 50),
               E(HOST, "python", "tpcc.gate", 1300, 100),
               E(HOST, "python", "tpcc.read", 1320, 80),
               E(HOST, "python", "tpcc.tally", 1400, 300),
               E(HOST, "python", "tpcc.finish", 1900, 80)]
    for chip in (0, 1):
        dev = f"/device:TPU:{chip}"
        ev += [E(dev, tr.MODULES, "jit_neworder_round(17)", 1100, 200),
               _op(chip, "fusion.1", 1100, 120, scopes and "si.read"),
               _op(chip, "all-reduce.3", 1200, 100, scopes and "si.commit"),
               _op(chip, "fusion.2", 1250, 50),
               E(dev, tr.MODULES, "jit_version_mover", 1750, 100),
               E(dev, tr.OPS, "fusion.9", 1750, 100),
               E(dev, tr.OPS, "fusion.0", 100, 50)]
    ev += [E(DEV, tr.MODULES, "jit_reduce_sum", 1600, 20),
           E(DEV, tr.OPS, "reduce.1", 1600, 20)]
    return ev


def test_idle_is_split_by_the_innermost_span():
    red = sr.reduce(_trace())
    # chip 0 idles 1000-1100, 1300-1600, 1620-1750, 1850-2000
    assert red.idle_s == pytest.approx(680e-9)
    want = {"tpcc.draw": 50e-9,                   # 1000-1050
            "tpcc.round": 10e-9 + 50e-9,          # 1050-1060, 1850-1900
            "bench.call.neworder": 40e-9,         # 1060-1100
            "tpcc.gate": 20e-9,                   # 1300-1320
            "tpcc.read": 80e-9,                   # 1320-1400, in the gate
            "tpcc.tally": 200e-9 + 80e-9,         # 1400-1600, 1620-1700
            "bench.call.version_mover": 50e-9,    # 1700-1750
            "tpcc.finish": 80e-9,                 # 1900-1980
            "driver": 20e-9}                      # 1980-2000
    assert red.idle_by_span == pytest.approx(want)
    assert sum(red.idle_by_span.values()) == pytest.approx(red.idle_s)
    # the gap 1300-1600 crosses the gate, its read and the tally; it is
    # named by the span open at its start
    gaps = dict((round(s * 1e9), n) for n, s in red.idle_gaps)
    assert gaps == {100: "tpcc.draw", 300: "tpcc.gate", 130: "tpcc.tally",
                    150: "tpcc.round"}
    assert red.idle_gaps[0] == ("tpcc.gate", pytest.approx(300e-9))


def test_scopes_spans_and_the_clock_check():
    red = sr.reduce(_trace())
    assert red.scoped == pytest.approx({"si.read": 120e-9,
                                        "si.commit": 100e-9})
    assert red.scope_source == "tf_op"
    assert red.spans["tpcc.round"] == (1, pytest.approx(900e-9))
    assert red.spans["tpcc.read"][0] == 1
    assert "bench.window" not in red.spans and "outside" not in red.spans
    assert red.first_op_stats[DEV] == ["hlo_module", "hlo_op", "tf_op"]
    # neworder runs 40 after its call opens, the mover 50 after its own
    assert red.call_lead_s == pytest.approx(-40e-9)
    early = [e._replace(start_ns=1040) if e.name == "jit_neworder_round(17)"
             else e for e in _trace()]
    assert sr.reduce(early).call_lead_s == pytest.approx(20e-9)


def test_scopes_from_compiled_hlo_text():
    text = ("HloModule jit_neworder_round, is_scheduled=true\n"
            "ENTRY %main {\n"
            '  %fusion.1 = s32[8]{0} fusion(%p), kind=kLoop, calls=%c1, '
            f'metadata={{op_name="{PATH}si.read/gather" '
            "stack_frame_id=3}\n"
            '  ROOT %all-reduce.3 = s32[8]{0} all-reduce(%x), '
            f'metadata={{op_name="{PATH}si.commit/psum"}}\n'
            "  %fusion.2 = s32[8]{0} fusion(%y), kind=kLoop\n}\n")
    hlo = sr.hlo_op_names([text])
    assert hlo == {("jit_neworder_round", "fusion.1"): PATH + "si.read/gather",
                   ("jit_neworder_round", "all-reduce.3"):
                   PATH + "si.commit/psum"}
    red = sr.reduce(_trace(scopes=False), hlo)
    assert red.scoped == pytest.approx({"si.read": 120e-9,
                                        "si.commit": 100e-9})
    assert red.scope_source == "hlo"


def test_a_scope_is_one_element_of_the_path():
    op = _op(0, "f", 0, 1, "xsi.read")
    assert sr._scope_of(op, None) == (None, None)
    op = _op(0, "f", 0, 1, "si.commit", key="long_name")
    assert sr._scope_of(op, None) == ("si.commit", "long_name")


def test_a_trace_of_the_program_without_spans_or_scopes():
    """The parent program: everything idle is the driver's or a call's,
    nothing is scoped, and the reduction does not fail."""
    red = sr.reduce(_trace(spans=False, scopes=False))
    assert set(red.idle_by_span) == {"driver", "bench.call.neworder",
                                     "bench.call.version_mover"}
    assert sum(red.idle_by_span.values()) == pytest.approx(red.idle_s)
    assert red.scoped == {"si.read": 0.0, "si.commit": 0.0}
    assert red.scope_source == "none"


def test_the_accepted_reduction_reads_the_same_with_the_program_spans():
    with_spans = tr.reduce(_trace())
    without = tr.reduce([tr.Event(*e[:5]) for e in _trace(spans=False)])
    assert with_spans == without
    ctx = types.SimpleNamespace(trace=with_spans, rounds=1, chips=2,
                                stats=None)
    ctx0 = ctx.__class__(trace=without, rounds=1, chips=2, stats=None)
    for m in ("driver.programs_per_round", "subround.device_ms",
              "mover.device_ms", "device.idle_share", "mesh.collective_ms"):
        assert spec.reader(m)(ctx) == spec.reader(m)(ctx0)


def test_segments_cut_a_span_that_outlives_its_parent():
    segs = sr._segments([(0, 10, "a"), (5, 20, "b")], 0, 30)
    assert segs == [(0, 5, "a"), (5, 10, "b"), (10, 30, "driver")]


# ---- the driver's spans in a real trace (XLA:CPU) --------------------------

@pytest.fixture
def traced_call(tiny_mix):
    """The tiny cell's warm-up call, then a traced 4-round call through the
    harness's instrumented driver, as ``run.run`` makes it."""
    drv = adapter.build(TINY, tiny_mix, 1)
    key_l, key_w, key_x = run.seed_keys(2**31 + 3)
    probe = adapter.Probe()
    n = 4
    with adapter.instrument(probe), tempfile.TemporaryDirectory() as d:
        st = adapter.load(drv, key_l)
        st, _ = adapter.call(drv, st, key_w, 3)
        jax.block_until_ready(st)
        jax.profiler.start_trace(d)
        probe.annotate = True
        try:
            with jax.profiler.TraceAnnotation(tr.WINDOW):
                st, stats = adapter.call(drv, st, key_x, n)
                jax.block_until_ready(st)
        finally:
            probe.annotate = False
            jax.profiler.stop_trace()
        events = sr.load(d)
    host = [e for e in events if e.plane.startswith("/host:")
            and sr.SPAN.match(e.name)]
    return n, stats, host


def _within(e, outer):
    return outer.start_ns <= e.start_ns and \
        e.start_ns + e.dur_ns <= outer.start_ns + outer.dur_ns


def test_driver_spans_and_the_read_counter(traced_call):
    n, stats, host = traced_call
    by = collections.defaultdict(list)
    for e in host:
        by[e.name].append(e)
    rounds = sorted(by["tpcc.round"], key=lambda e: e.start_ns)
    assert [e.stats["round"] for e in rounds] == list(range(n))
    assert len(by["tpcc.start"]) == len(by["tpcc.finish"]) == 1
    outer = rounds + by["tpcc.start"] + by["tpcc.finish"]
    for name in ("tpcc.read", "tpcc.draw", "tpcc.gate", "tpcc.tally"):
        for e in by[name]:
            assert any(_within(e, o) for o in outer), (name, e)
    assert len(by["tpcc.draw"]) == n
    assert len(by["tpcc.gate"]) == 5 * n
    assert len(by["tpcc.read"]) == stats.host_reads > 0
    # jax.transfer_guard_device_to_host("disallow") does not fire on XLA:CPU
    # (its arrays already live in host memory), so a read that bypasses the
    # counter is caught by the arithmetic instead: a write type's program
    # costs 12 reads (its gate, 5 counts, 6 op counts), delivery one more,
    # a read-only type's 7 (its gate, 6 op counts), a type not drawn its
    # gate alone; each round reads the overflow peak once, and the call's
    # end 5 pending retries
    calls = collections.Counter(e.name[len("bench.call."):] for e in host
                                if e.name.startswith("bench.call."))
    assert calls["version_mover"] == n
    writes = calls["neworder"] + calls["payment"] + calls["delivery"]
    reads = calls["orderstatus"] + calls["stocklevel"]
    assert stats.host_reads == (6 * n + 11 * writes + calls["delivery"]
                                + 6 * reads + 5)


def test_the_counter_exists_with_tracing_off(tiny_mix):
    drv = adapter.build(TINY, tiny_mix, 1)
    key_l, key_w, _ = run.seed_keys(9)
    st = adapter.load(drv, key_l)
    _, stats = adapter.call(drv, st, key_w, 2)
    assert isinstance(stats, tpcc.MixedRunStats)
    assert stats.host_reads >= 2 * 6 + 5
