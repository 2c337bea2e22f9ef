"""Subprocess body for the mesh case of the read-only lane compaction:
4 forced host devices.

Loads a small store with orders on one shard, partitions it over a 4-way
'mem' mesh (pool and timestamp vector), and runs the read-only programs
through the mesh's read executor at active counts of one, two and many
chunks. Each must equal the masked full-width round function through the
same executor, and the single-shard program, exactly: answers, ``found``,
read sets and op counts.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp

import _readonly_common as roc
from repro import compat
from repro.core.tsoracle import PartitionedVectorOracle
from repro.db import tpcc


def main():
    assert len(jax.devices()) == 4, jax.devices()
    oracle = PartitionedVectorOracle(roc.CFG.n_threads, n_parts=4)
    lay, st_s = roc.loaded(oracle)
    engine = tpcc.make_mixed_engine(roc.CFG, lay, compat.mesh(4), "mem",
                                    oracle, shard_vector=True)
    st_d = tpcc.distribute_state(engine, st_s)
    single = tpcc._sub_rounds(roc.CFG, lay, oracle, None, roc.LAST_N)
    mesh = tpcc._sub_rounds(roc.CFG, lay, oracle, engine, roc.LAST_N)
    assert max(roc.COUNTS) > 2 * roc.K     # many chunks among the counts
    for name in roc.NAMES:
        inp = roc.inputs(name)
        full = roc.full_width(name, lay, oracle, engine)
        for n in roc.COUNTS:
            act = roc.active(n)
            got = mesh[name](st_d, inp, jnp.asarray(act))
            roc.assert_same_answers(got, full(st_d, inp, jnp.asarray(act)),
                                    act)
            roc.assert_same_answers(got, single[name](st_s, inp,
                                                      jnp.asarray(act)), act)
        print(f"{name}: mesh compacted == mesh full width == single shard "
              f"at {roc.COUNTS} active lanes")
    print("READONLY_CHUNKS_OK")


if __name__ == "__main__":
    main()
