"""The adapter's promises, at a tiny size on the CPU: the programs are
built once, a round is timed once, a second driver call compiles nothing,
the jitted loader loads what the engine's own loader loads, and the
traffic guard sees a changed generator."""
import time

import jax
import numpy as np
import pytest

import adapter
import run
import traffic
from conftest import TINY
from repro.db import tpcc, workload


@pytest.fixture
def driver(tiny_mix):
    return adapter.build(TINY, tiny_mix, 1)


def test_programs_are_built_once_and_restored(driver):
    real = tpcc._sub_rounds
    with adapter.instrument(adapter.Probe()):
        args = (driver.cfg, driver.lay, driver.oracle, None, 8)
        assert tpcc._sub_rounds(*args) is tpcc._sub_rounds(*args)
        assert tpcc._sub_rounds(*args[:-1], 4) is not \
            tpcc._sub_rounds(*args)
    assert tpcc._sub_rounds is real


def test_one_timestamp_a_round_and_no_compile_in_a_second_call(driver):
    key_l, key_w, key_x = run.seed_keys(7)
    probe, clog = adapter.Probe(), adapter.CompileCounter()
    with adapter.instrument(probe):
        st = adapter.load(driver, key_l)
        st, _ = adapter.call(driver, st, key_w, 4)
        assert len(probe.round_times) == 4
        probe.reset()
        before = len(clog.names)
        st, _ = adapter.call(driver, st, key_x, 3)
        jax.block_until_ready(st)
        assert len(clog.names) == before
        assert len(probe.round_times) == 3
        assert probe.round_times == sorted(probe.round_times)


def test_without_the_memo_a_second_call_lowers_again(driver):
    key_l, key_w, key_x = run.seed_keys(7)
    clog = adapter.CompileCounter()
    st = adapter.load(driver, key_l)
    st, _ = adapter.call(driver, st, key_w, 4)
    before = len(clog.names)
    adapter.call(driver, st, key_x, 2)
    assert len(clog.names) > before


def test_compile_seconds_are_counted_where_they_end(driver):
    key_l = run.seed_keys(8)[0]
    clog = adapter.CompileCounter()
    t0 = time.perf_counter()
    adapter.load(driver, key_l)
    t1 = time.perf_counter()
    assert 0 < clog.seconds_between(t0, t1) <= t1 - t0
    assert clog.seconds_between(t1, time.perf_counter()) == 0


def test_the_window_check_refuses_a_miscount_or_a_compile():
    run.check_window(3, [0.0, 1.0, 2.0, 3.0], [])
    with pytest.raises(RuntimeError, match="timed 2 rounds"):
        run.check_window(3, [0.0, 1.0, 2.0], [])
    with pytest.raises(RuntimeError, match="compiles inside the window"):
        run.check_window(3, [0.0, 1.0, 2.0, 3.0], ["jit(payment_round)"])


def test_jitted_loader_is_bit_identical_to_the_engines(driver):
    key = run.seed_keys(2**31 + 5)[0]
    a = adapter.loader(driver)(key)
    b = adapter.load_op_by_op(driver, key)
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and np.array_equal(np.asarray(x),
                                                     np.asarray(y))


def test_the_guard_passes_the_engines_draws(driver, tiny_mix):
    cfg = driver.cfg
    for sub in traffic.round_keys(run.seed_keys(11)[2], 2):
        traffic.guard(adapter.program_draws(driver, sub),
                      traffic.draw(sub, tiny_mix, cfg.n_threads,
                                   cfg.n_warehouses, cfg.n_items,
                                   cfg.customers_per_district))


def test_the_guard_catches_a_changed_draw(driver, tiny_mix, monkeypatch):
    real = workload.gen_payment

    def richer(*a, **kw):
        p = real(*a, **kw)
        return p._replace(amount=p.amount + 1)

    monkeypatch.setattr(workload, "gen_payment", richer)
    cfg = driver.cfg
    sub = traffic.round_keys(run.seed_keys(11)[2], 1)[0]
    with pytest.raises(RuntimeError, match="payment.amount"):
        traffic.guard(adapter.program_draws(driver, sub),
                      traffic.draw(sub, tiny_mix, cfg.n_threads,
                                   cfg.n_warehouses, cfg.n_items,
                                   cfg.customers_per_district))
