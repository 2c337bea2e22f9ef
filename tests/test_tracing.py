"""The protocol's named scopes reach the compiled programs: the HLO of a
write sub-round carries ``si.read`` and ``si.commit`` in its instructions'
``op_name``, stock-level's carries ``si.read``; single-shard here, and on a
4-device mesh in a process of its own (the host device count is fixed
before JAX starts)."""
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp

from repro.core.tsoracle import PartitionedVectorOracle, VectorOracle
from repro.db import tpcc, workload

SCOPE = re.compile(r'op_name="[^"]*?(?:^|/)(si\.read|si\.commit)(?:/|")')
CFG = tpcc.TPCCConfig(n_warehouses=2, customers_per_district=8, n_items=64,
                      n_threads=16, orders_per_thread=8)


def scopes_of_programs(n_shards: int) -> dict:
    """``{program: scopes in its compiled HLO}`` for new-order, payment
    and stock-level, compiled from shapes alone."""
    if n_shards > 1:
        from repro import compat
        oracle = PartitionedVectorOracle(CFG.n_threads, n_parts=n_shards)
        lay = tpcc.make_layout(CFG)
        engine = tpcc.make_mixed_engine(CFG, lay, compat.mesh(n_shards),
                                        "mem", oracle, shard_vector=True)
    else:
        oracle, engine = VectorOracle(CFG.n_threads), None
    lay, st = tpcc.init_tpcc(CFG, oracle, jax.random.PRNGKey(0))
    if engine is not None:
        st = tpcc.distribute_state(engine, st)   # the padded pool
    inp = workload.gen_mixed(
        jax.random.PRNGKey(1), CFG.n_threads, CFG.n_warehouses, CFG.n_items,
        CFG.customers_per_district, None, CFG.dist_degree,
        workload.zipf_logits(CFG.n_items, CFG.skew_alpha), None)
    progs = tpcc._sub_rounds(CFG, lay, oracle, engine, 20)

    def shape(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            tree)

    act = jax.ShapeDtypeStruct((CFG.n_threads,), jnp.bool_)
    st = shape(st)
    lowered = {
        "neworder": progs["neworder"].lower(st, shape(inp.neworder), 0, act,
                                            None),
        "payment": progs["payment"].lower(st, shape(inp.payment), 0, act,
                                          None),
        "stocklevel": progs["stocklevel"].lower(st, shape(inp.stocklevel),
                                                act)}
    return {k: set(SCOPE.findall(v.compile().as_text()))
            for k, v in lowered.items()}


WANT = {"neworder": {"si.read", "si.commit"},
        "payment": {"si.read", "si.commit"},
        "stocklevel": {"si.read"}}


def test_scopes_in_the_single_shard_programs():
    assert scopes_of_programs(1) == WANT


def test_scopes_in_the_mesh_programs():
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join(
               [src] + [p for p in [os.environ.get("PYTHONPATH")] if p])}
    out = subprocess.run([sys.executable, __file__], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SCOPES_OK" in out.stdout


if __name__ == "__main__":
    assert len(jax.devices()) == 4
    got = scopes_of_programs(4)
    assert got == WANT, got
    print("SCOPES_OK", got)
