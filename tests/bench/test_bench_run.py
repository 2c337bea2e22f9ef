"""Whole runs of a tiny cell through the harness's own code path on the CPU
(the chip check skipped): sound runs compare exactly, and every planted
fault, the control and the order index's overflow read as not correct."""
import jax.numpy as jnp
import numpy as np
import pytest

import control
import run
from conftest import TINY, write_cell
from repro.core import rangeindex as ri


def _run(cell, seed, **kw):
    return run.run(cell, seed, 60.0, False, allow_cpu=True, **kw)


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 2**40 + 9])
def test_sound_runs_compare_exactly(tiny_cell, seed):
    r = _run(tiny_cell, seed)
    assert r["correct"], r["checks"]
    assert all(c == {"value": 0, "limit": 0} for c in r["checks"].values())
    assert list(r["checks"]) == ["stats", "store", "index", "answers"]
    assert list(r)[-1] == "checks"
    assert r["attempted"] > r["failed"] >= 0
    assert set(r["metrics"]) == {"txn_per_s", "neworder_per_s",
                                 "round_ms_mean", "setup_s"}


def test_the_cap_leaves_no_insert_behind(tmp_path, monkeypatch):
    """A window asked for far more rounds than the extends hold is cut, and
    every committed new-order still inserts its order."""
    cell = write_cell(tmp_path, monkeypatch, sizes={"orders_per_thread": 6})
    r = run.run(cell, 5, 1e6, False, allow_cpu=True)
    assert r["correct"], r["checks"]


def _unchanged(prog, st, *a, **kw):
    return prog(st, *a, **kw)._replace(state=st)


def _half_batch(prog, st, inp, round_no, active, journal):
    half = active & (jnp.arange(active.shape[0]) < active.shape[0] // 2)
    out = prog(st, inp, round_no, half, journal)
    return out._replace(committed=out.committed | (active & ~half))


def _altered_answer(prog, st, inp, active):
    out = prog(st, inp, active)
    return out._replace(result=out.result + active.astype(out.result.dtype))


def _altered_install(prog, st, *a, **kw):
    out = prog(st, *a, **kw)
    tbl = out.state.nam.table
    tbl = tbl._replace(cur_data=tbl.cur_data.at[0, 0].add(1))
    return out._replace(state=out.state._replace(
        nam=out.state.nam._replace(table=tbl)))


@pytest.mark.parametrize("fault", [
    {"payment": _unchanged},
    {"neworder": _half_batch},
    {"stocklevel": _altered_answer},
    {"delivery": _altered_install},
], ids=["state_unchanged", "half_batch", "answer_altered",
        "install_altered"])
def test_a_planted_fault_is_not_correct(tiny_cell, fault):
    r = _run(tiny_cell, 3, corrupt=fault)
    assert not r["correct"]
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_a_mover_that_moves_nothing_is_not_correct(tiny_cell):
    r = _run(tiny_cell, 3, corrupt={"version_mover":
                                    lambda mover, tbl, **kw: tbl})
    assert not r["correct"] and r["checks"]["store"]["value"] > 0


def test_the_control_breaks_isolation_and_reads_wrong(tiny_mix):
    """The control: the reference with every write granted (lost updates)
    in the program's place, at the tiny size, on three seeds."""
    for seed in (3, 4, 2**33 + 1):
        r = control.readings(TINY, tiny_mix, seed, 4)
        assert r["store"][0] > r["store"][1]
        assert r["stats"][0] > r["stats"][1]


def test_a_full_order_index_drops_orders(tiny_cell, monkeypatch):
    """An order index that drops orders once it is full (planted: inserts
    past 8 entries are discarded) leaves delivery, order-status and
    stock-level without them, and the comparison says so."""
    insert = ri.insert

    def full_at_8(idx, keys, vals, mask=None):
        room = idx.delta_used < 8
        return insert(idx, keys, vals,
                      mask=room if mask is None else mask & room)

    monkeypatch.setattr(ri, "insert", full_at_8)
    r = _run(tiny_cell, 3)
    assert not r["correct"] and r["checks"]["index"]["value"] > 0


def test_the_index_with_merges_keeps_every_order():
    """The second witness: the engine's own merge, applied when the delta
    is full, keeps every key, as the reference does."""
    keys = jnp.arange(40, dtype=jnp.uint32)[::-1]
    idx = ri.build(jnp.zeros((0,), jnp.uint32), jnp.zeros((0,), jnp.int32),
                   capacity=64, delta_capacity=8)
    merged = idx
    for k in range(0, 40, 4):
        batch = keys[k:k + 4]
        idx = ri.insert(idx, batch, batch.astype(jnp.int32))
        if int(merged.delta_used) + 4 > 8:
            merged = ri.merge(merged)
        merged = ri.insert(merged, batch, batch.astype(jnp.int32))

    def held(i):
        k = np.concatenate([np.asarray(i.base_keys),
                            np.asarray(i.delta_keys)])
        return set(k[k != np.uint32(0xFFFFFFFF)].tolist())

    assert len(held(idx)) == 8
    assert held(merged) == set(range(40))
