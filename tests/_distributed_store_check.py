"""Subprocess body for test_distributed_store: runs with 8 host devices.

Executes the same workload twice — single-device si.run_round vs. the
shard_map distributed_round over an 8-way 'mem' mesh — and asserts identical
committed sets and identical final table state (the distribution layer must
be semantics-preserving).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core import mvcc, si, store
from repro.core.tsoracle import VectorOracle


def main():
    assert len(jax.devices()) == 8, jax.devices()
    mesh = compat.mesh(8)
    n_records, width, n_threads = 64, 4, 16
    shard_records = n_records // 8
    oracle = VectorOracle(n_threads)

    def compute_fn(rh, rd, vec, aux):
        return rd[:, :1, :].at[..., 0].add(1)

    round_fn, _ = store.distributed_round(mesh, "mem", oracle, compute_fn,
                                          shard_records)

    tbl_d = store.shard_table(mesh, "mem",
                              mvcc.init_table(n_records, width, 2, 2))
    tbl_s = mvcc.init_table(n_records, width, 2, 2)
    st = oracle.init()
    vec_d = st.vec
    key = jax.random.PRNGKey(7)
    for rnd in range(6):
        key, sub = jax.random.split(key)
        slots = jax.random.randint(sub, (n_threads, 2), 0, n_records,
                                   dtype=jnp.int32)
        batch = si.TxnBatch(
            tid=jnp.arange(n_threads, dtype=jnp.int32),
            read_slots=slots,
            read_mask=jnp.ones((n_threads, 2), bool),
            write_ref=jnp.zeros((n_threads, 1), jnp.int32),
            write_mask=jnp.ones((n_threads, 1), bool),
        )
        tbl_d, vec_d, dout = round_fn(tbl_d, vec_d, batch, None)
        out = si.run_round(tbl_s, oracle, st, batch,
                           lambda rh, rd, vec: compute_fn(rh, rd, vec, None))
        tbl_s, st = out.table, out.oracle_state
        np.testing.assert_array_equal(np.asarray(dout.committed),
                                      np.asarray(out.committed),
                                      err_msg=str(rnd))
        tbl_s = mvcc.version_mover(tbl_s)
        # the version-mover is per-record elementwise, so it runs directly on
        # the sharded table (XLA preserves the record-axis sharding)
        tbl_d = jax.jit(mvcc.version_mover)(tbl_d)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(tbl_d.cur_data)),
            np.asarray(tbl_s.cur_data))
    np.testing.assert_array_equal(np.asarray(vec_d), np.asarray(st.vec))
    print("DISTRIBUTED_OK")


if __name__ == "__main__":
    main()
