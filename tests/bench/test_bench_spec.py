"""The benchmark's data: BENCHMARK.json within the contract's character
rules, and every configuration, traffic mix and per-layer metric found by
its name."""
import json
import os
import re
import types

import pytest

import reference
import spec
import traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_benchmark_names_units_and_lines():
    b = spec.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in b["paths"])
    assert all(_line(w) for w in b["command"])
    assert 1 <= b["run_seconds"] <= 51
    for c in b["configs"]:
        assert NAME.match(c["name"]) and _line(c["source"]) \
            and _line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["config"]) \
            and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["chips"] in (1, 4)
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert "setup_s" in {m["name"] for m in b["end_to_end"]}
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert _line(m["layer"]) and m["moves"] in e2e
    assert len(json.dumps(b)) < 64 * 1024


def test_cells_configs_mixes_and_metrics_load_by_name():
    b = spec.benchmark()
    for w in b["workloads"]:
        cell = spec.cell(w["name"])
        assert (cell.config, cell.traffic, cell.chips) == (
            w["config"], w["traffic"], w["chips"])
        conf = spec.config(cell.config)
        assert conf["name"] == cell.config
        assert set(conf["reduced"]) <= set(conf)
        mix = traffic.load_mix(spec.traffic_path(cell.traffic))
        assert mix.warmup_rounds >= 3
        assert spec.per_layer(cell.name)
    for c in b["configs"]:
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    for m in b["per_layer"]:
        assert callable(spec.reader(m["name"]))


def test_a_cell_comes_only_from_the_benchmark():
    """``BENCHMARK.json`` is the one list of cells: a name made of a
    configuration and a mix that both have files, but that is not a cell
    there, does not run."""
    conf = spec.benchmark()["configs"][0]["name"]
    assert os.path.exists(spec.traffic_path("mix"))
    with pytest.raises(KeyError):
        spec.cell(conf + ".no-such-mix")
    with pytest.raises(KeyError):
        spec.cell("no-such-config.mix")


def test_every_cut_names_what_the_source_states():
    """Each key a configuration lists in ``reduced`` has its value in the
    file and the source's value beside it; each departure the program
    forces names why."""
    for c in spec.benchmark()["configs"]:
        conf = spec.config(c["name"])
        for k in c["reduced"]:
            assert k in conf and k in conf["spec_values"], k
        assert set(conf["program_limits"]) - set(c["reduced"]) \
            <= {"record_bytes"}
        assert all(isinstance(v, str) and v
                   for v in conf["program_limits"].values())


def test_peaks_by_device_kind():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_readers_read_nothing_without_a_trace():
    stats = {"attempts": {t: 10 for t in reference.TYPES},
             "commits": {t: 4 for t in reference.TYPES}}
    ctx = types.SimpleNamespace(trace=None, stats=stats, rounds=5, chips=1)
    values = {m["name"]: spec.reader(m["name"])(ctx)
              for m in spec.benchmark()["per_layer"]}
    assert values.pop("si.commits_per_attempt") == pytest.approx(0.4)
    assert all(v is None for v in values.values())


def test_mix_file_rejects_a_short_warmup(tmp_path):
    d = json.load(open(spec.traffic_path("mix")))
    d["warmup_rounds"] = 2
    (tmp_path / "m.json").write_text(json.dumps(d))
    with pytest.raises(ValueError):
        traffic.load_mix(str(tmp_path / "m.json"))
