"""The trace reduction, on a small trace written here: two chips, a window
span, program calls on the host and one collective."""
import types

import pytest

import spec
import trace_reduce as tr

E = tr.Event
HOST = "/host:CPU"


def _trace():
    ev = [E(HOST, "python", tr.WINDOW, 1000, 1000),
          E(HOST, "python", "bench.call.neworder", 1000, 300),
          E(HOST, "python", "bench.call.version_mover", 1700, 100),
          E(HOST, "python", "outside", 0, 10)]
    for chip in (0, 1):
        dev = f"/device:TPU:{chip}"
        ev += [E(dev, tr.MODULES, "jit_neworder_round(17)", 1100, 200),
               E(dev, tr.OPS, "fusion.1", 1100, 120),
               E(dev, tr.OPS, "all-reduce.3", 1200, 100),
               E(dev, tr.OPS, "fusion.2", 1250, 50),    # overlaps
               E(dev, tr.MODULES, "jit_version_mover", 1750, 100),
               E(dev, tr.OPS, "fusion.9", 1750, 100),
               E(dev, tr.MODULES, "jit_reduce_sum", 1900, 20),
               E(dev, tr.OPS, "reduce.1", 1900, 20),
               E(dev, tr.OPS, "fusion.0", 100, 50)]     # before the window
    return ev


def test_busy_programs_collectives_and_gaps():
    red = tr.reduce(_trace())
    assert red.chips == 2
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(320e-9)       # 200 + 100 + 20
    assert red.collective_s == pytest.approx(100e-9)
    assert red.programs == {"neworder_round": (1, 200e-9),
                            "version_mover": (1, 100e-9),
                            "reduce_sum": (1, 20e-9)}
    assert red.runs == [3, 3]
    gaps = dict((round(s * 1e9), n) for n, s in red.idle_gaps)
    # 1000-1100 inside the neworder call, 1300-1750 in the driver,
    # 1850-1900 and 1920-2000 in the driver after the mover's call
    assert gaps == {100: "bench.call.neworder", 450: "driver",
                    50: "driver", 80: "driver"}
    assert red.idle_gaps[0] == ("driver", pytest.approx(450e-9))
    bd = tr.breakdown(red, top=2)
    assert bd["device_ops"] == [["neworder_round", pytest.approx(200e-9)],
                                ["version_mover", pytest.approx(100e-9)]]
    assert len(bd["idle_gaps"]) == 2


def test_readers_over_the_reduction():
    red = tr.reduce(_trace())
    ctx = types.SimpleNamespace(trace=red, rounds=1, chips=2, stats=None)
    read = {m: spec.reader(m)(ctx) for m in (
        "driver.programs_per_round", "subround.device_ms", "mover.device_ms",
        "device.idle_share", "mesh.collective_ms")}
    assert read == pytest.approx({
        "driver.programs_per_round": 3, "subround.device_ms": 200e-6,
        "mover.device_ms": 100e-6, "device.idle_share": 0.68,
        "mesh.collective_ms": 100e-6})


def test_a_trace_without_window_or_chip_is_refused():
    with pytest.raises(ValueError):
        tr.reduce([e for e in _trace() if e.name != tr.WINDOW])
    with pytest.raises(ValueError):
        tr.reduce([e for e in _trace() if e.plane == HOST])
