"""Compile the engine's chip programs for a described TPU v5e, without a chip.

The TPU compiler ships with JAX and compiles for a topology that is only
described, so what it would refuse on the chip (a program too large for the
device, a kernel Mosaic cannot lower) fails here at no chip time. Nothing
runs: these cases say nothing about results or times.

* the mixed driver's jitted sub-round programs of the one-chip path, at the
  size ``chip_smoke.py`` runs (TPC-C cardinalities, 40 warehouses, 10 lanes
  per warehouse);
* the same programs over a described 4-chip mesh, where write types run
  through ``store.distributed_round`` and read-only types through
  ``store.distributed_readonly_round``, at the four-chip phase's size;
* the two Pallas kernels, which Mosaic does not lower yet: strict xfails
  that name the compiler's refusal, so they flip when a kernel compiles.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.core.tsoracle import PartitionedVectorOracle, VectorOracle
from repro.db import tpcc, workload

SPEC = dict(n_items=100_000, customers_per_district=3_000)
ONE_CHIP_WAREHOUSES = 40     # chip_smoke.py's spec phase
MESH_WAREHOUSES = 4          # chip_smoke.py's four-chip phase
LANES_PER_WAREHOUSE = 10
ROUNDS = 10
TYPES = ("neworder", "payment", "delivery", "orderstatus", "stocklevel")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a described chip's compiles are written to the persistent cache but
    # cannot be read back without one: keep them out of it
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return jax.make_mesh((4,), ("mem",), axis_types=(AxisType.Auto,),
                         devices=topo.devices[:4])


def _config(n_warehouses, layout="table_major"):
    return tpcc.TPCCConfig(n_warehouses=n_warehouses,
                           n_threads=LANES_PER_WAREHOUSE * n_warehouses,
                           orders_per_thread=ROUNDS, layout=layout, **SPEC)


def _shapes(cfg, oracle):
    """Abstract loaded state and one round's inputs (nothing allocated)."""
    def load(key):
        return tpcc.init_tpcc(cfg, oracle, key)[1]

    def draw(key):
        return workload.gen_mixed(
            key, cfg.n_threads, cfg.n_warehouses, cfg.n_items,
            cfg.customers_per_district, None, cfg.dist_degree,
            workload.zipf_logits(cfg.n_items, None))

    key = jax.random.PRNGKey(0)
    return jax.eval_shape(load, key), jax.eval_shape(draw, key)


def _placed(tree, sharding_of):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding_of(s)), tree)


def _args(kind, st, inp, cfg, sharding):
    act = jax.ShapeDtypeStruct((cfg.n_threads,), jnp.bool_,
                               sharding=sharding)
    inputs = _placed(getattr(inp, kind), lambda s: sharding)
    if kind in ("orderstatus", "stocklevel"):
        return (st, inputs, act)
    round_no = jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)
    return (st, inputs, round_no, act, None)


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    print(compiled.memory_analysis())
    return compiled


@functools.lru_cache(maxsize=None)
def _one_chip_problem():
    cfg = _config(ONE_CHIP_WAREHOUSES)
    oracle = VectorOracle(cfg.n_threads)
    lay = tpcc.make_layout(cfg)
    st, inp = _shapes(cfg, oracle)
    return cfg, lay, oracle, st, inp


@pytest.mark.parametrize("kind", TYPES)
def test_one_chip_sub_round_compiles(kind, one_chip):
    cfg, lay, oracle, st, inp = _one_chip_problem()
    assert lay.catalog.total_records * 208 > 1e9   # > 1 GB of store
    programs = tpcc._sub_rounds(cfg, lay, oracle, None, 8)
    st = _placed(st, lambda s: one_chip)
    _compile(programs[kind], *_args(kind, st, inp, cfg, one_chip))


@pytest.mark.parametrize("n_warehouses", [ONE_CHIP_WAREHOUSES,
                                          MESH_WAREHOUSES, 2])
def test_one_chip_version_mover_compiles(n_warehouses, one_chip):
    """At every size chip_smoke.py loads on one chip. As table-wide
    gathers/scatters with one index per record, the mover took the TPU
    compiler 250 s at 2 warehouses and 520 s at 4, against 5 s at 40."""
    cfg = _config(n_warehouses)
    st, _ = _shapes(cfg, VectorOracle(cfg.n_threads))
    _compile(tpcc._version_mover, _placed(st.nam.table, lambda s: one_chip))


@pytest.mark.parametrize("kind", TYPES)
@pytest.mark.parametrize("layout", ["warehouse_major", "table_major"])
def test_mesh_sub_round_compiles(layout, kind, mesh4):
    """Four memory servers: the pool and the timestamp vector partitioned
    over the chips, everything else replicated."""
    cfg = _config(MESH_WAREHOUSES, layout)
    oracle = PartitionedVectorOracle(cfg.n_threads, n_parts=4)
    lay = tpcc.make_layout(cfg)
    engine = tpcc.make_mixed_engine(cfg, lay, mesh4, "mem", oracle,
                                    shard_vector=True)
    st, inp = _shapes(cfg, oracle)
    padded = -(-lay.catalog.total_records // 4) * 4
    parted = NamedSharding(mesh4, P("mem"))
    replicated = NamedSharding(mesh4, P())
    table = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        (padded,) + s.shape[1:], s.dtype, sharding=parted), st.nam.table)
    vec = _placed(st.nam.oracle_state.vec, lambda s: parted)
    st = _placed(st, lambda s: replicated)
    st = st._replace(nam=st.nam._replace(
        table=table, oracle_state=st.nam.oracle_state._replace(vec=vec)))
    programs = tpcc._sub_rounds(cfg, lay, oracle, engine, 8)
    compiled = _compile(programs[kind],
                        *_args(kind, st, inp, cfg, replicated))
    text = compiled.as_text()
    assert "all-reduce" in text or "all-gather" in text
    if kind == "neworder":    # the mover runs on each memory server's rows
        compiled = _compile(tpcc._version_mover, table)
        assert "all-reduce" not in compiled.as_text()


def _kernel_refusal(build, expected):
    """Compile a kernel for the described chip; re-raise Mosaic's refusal
    only if it is the one recorded in the kernels' README, so the strict
    xfail cannot pass on some other error."""
    try:
        build().compile()
    except NotImplementedError as e:
        if expected not in str(e):
            raise AssertionError(f"a different refusal: {e}") from e
        raise


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="Mosaic has no vector scatter-min: 'Unimplemented "
                   "primitive in Pallas TPU lowering ...: scatter-min' "
                   "(the CAS tournament's .at[].min in commit/kernel.py)")
def test_commit_kernel_compiles(one_chip):
    from repro.kernels.commit.kernel import fused_commit
    R, K, T, WS = 1 << 16, 8, 1024, 8
    Q = T * WS
    s = functools.partial(_sds, sharding=one_chip)
    u32, i32, b = jnp.uint32, jnp.int32, jnp.bool_
    args = (s((R, 2), u32), s((R * K, 2), u32), s((R,), i32), s((T,), u32),
            s((Q,), i32), s((Q, 2), u32), s((Q,), u32), s((Q,), b),
            s((Q,), i32), s((Q, 2), u32), s((T,), b), s((T,), i32),
            s((T,), u32), s((T,), i32))
    _kernel_refusal(lambda: jax.jit(lambda *a: fused_commit(
        *a, n_old=K, interpret=False)).lower(*args), "scatter-min")


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="Mosaic lowers only 2-D gathers: 'Only 2D gather "
                   "is supported' (the 1-D table gathers in "
                   "hash_probe/kernel.py)")
def test_hash_probe_kernel_compiles(one_chip):
    from repro.kernels.hash_probe.kernel import hash_probe
    B, R, K, KO, n_vec, Q = 1 << 16, 1 << 16, 4, 8, 1024, 1024
    s = functools.partial(_sds, sharding=one_chip)
    u32, i32 = jnp.uint32, jnp.int32
    args = (s((B,), u32), s((B,), i32), s((R,), u32), s((R,), u32),
            s((R * K,), u32), s((R * K,), u32), s((R,), i32),
            s((R * KO,), u32), s((R * KO,), u32), s((R,), i32),
            s((n_vec,), u32), s((Q,), u32))
    _kernel_refusal(lambda: jax.jit(lambda *a: hash_probe(
        *a, n_old=K, n_ovf=KO, bq=256, max_probes=16,
        interpret=False)).lower(*args), "Only 2D gather")


def test_store_bytes_match_record_size():
    """208 B per record at the engine's widths: 8 B header + 32 B payload,
    in place and in each of 2 old and 2 overflow slots, plus 2 counters."""
    cfg = _config(1)
    st, _ = _shapes(cfg, VectorOracle(cfg.n_threads))
    tbl = st.nam.table
    per_record = sum(np.prod(x.shape[1:]) * x.dtype.itemsize
                     for x in jax.tree.leaves(tbl))
    assert per_record == 208
