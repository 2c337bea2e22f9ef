"""Shared set-up of the benchmark's CPU tests: the benchmark's modules on
the path, and a tiny cell (2 warehouses, 1,000 items, 30 customers a
district) that runs through the harness's own code path in seconds."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench"))

import spec  # noqa: E402
import traffic  # noqa: E402

TINY = dict(n_warehouses=2, n_items=1000, customers_per_district=30,
            orders_per_thread=8, n_old_versions=2, n_overflow=2,
            layout="table_major")
# even shares, so the warm-up draws every type
TINY_MIX = dict(name="tiny", lanes_per_warehouse=8, dist_degree=10.0,
                remote_payment_frac=0.15, warmup_rounds=3, stock_last_n=20,
                locality_mode=None,
                mix={"neworder": 0.3, "payment": 0.3, "orderstatus": 0.13,
                     "delivery": 0.14, "stocklevel": 0.13})


def write_cell(tmp_path, monkeypatch, *, sizes=None, mix=None):
    """Point the harness at a tiny cell ``tiny.mix`` written under
    ``tmp_path``; returns its name."""
    sizes = {**TINY, **(sizes or {})}
    mix = {**TINY_MIX, **(mix or {})}
    (tmp_path / "mix.json").write_text(json.dumps(mix))
    monkeypatch.setattr(spec, "config", lambda name: sizes)
    monkeypatch.setattr(spec, "traffic_path",
                        lambda name: str(tmp_path / "mix.json"))
    monkeypatch.setattr(spec, "cell",
                        lambda name: spec.Cell(name, "tiny", "mix", 1))
    return "tiny.mix"


@pytest.fixture
def tiny_cell(tmp_path, monkeypatch):
    return write_cell(tmp_path, monkeypatch)


@pytest.fixture
def tiny_mix(tmp_path):
    (tmp_path / "mix.json").write_text(json.dumps(TINY_MIX))
    return traffic.load_mix(str(tmp_path / "mix.json"))
