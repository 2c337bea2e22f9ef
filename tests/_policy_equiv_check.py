"""Subprocess body: opt-policy sharded paths == baseline numerics.

Run on 16 host devices (mesh 4x4 data x model). Checks, per policy knob,
that the optimized path computes the same values as the baseline path:
  * embed_lookup (shard_map local gather)  — exact equality
  * apply_moe (shard_map local dispatch)   — same routing & math per shard
    (local capacity changes which tokens drop under overflow, so we use a
    capacity factor that is dropless in both paths)
  * kv_cache_update (owner-shard write)    — exact equality
  * end-to-end train_loss of a reduced MoE arch — close (f32 reduction
    order differs across shards)
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro import policy
from repro.models import common
from repro.models import moe as moe_mod

mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)


def check_embed():
    key = jax.random.PRNGKey(0)
    emb = jax.random.normal(key, (64, 32), jnp.float32)
    tok = jax.random.randint(jax.random.PRNGKey(1), (8, 6), 0, 64)
    policy.set_policy("baseline")
    ref = jax.jit(common.embed_lookup)(emb, tok)
    policy.set_policy("opt")
    with mesh:
        out = jax.jit(common.embed_lookup)(emb, tok)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    print("embed_lookup OK")


def check_moe():
    key = jax.random.PRNGKey(2)
    p = moe_mod.init_moe(key, 32, 64, 4, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (16, 32), jnp.float32)

    policy.set_policy("baseline")
    y_ref, st_ref = jax.jit(
        lambda p, x: moe_mod.apply_moe(p, x, top_k=2, capacity_factor=8.0)
    )(p, x)
    policy.set_policy("opt")
    with mesh:
        y, st = jax.jit(
            lambda p, x: moe_mod.apply_moe(p, x, top_k=2,
                                           capacity_factor=8.0))(p, x)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(st_ref.load),
                                  np.asarray(st.load))
    assert float(st.dropped_fraction) == 0.0
    print("apply_moe OK")


def check_kv_update():
    B, S, H, Dh = 8, 16, 2, 4
    kc = jnp.zeros((B, S, H, Dh), jnp.bfloat16)
    vc = jnp.zeros((B, S, H, Dh), jnp.bfloat16)
    kn = jax.random.normal(jax.random.PRNGKey(4), (B, H, Dh), jnp.bfloat16)
    vn = jax.random.normal(jax.random.PRNGKey(5), (B, H, Dh), jnp.bfloat16)
    pos = jax.random.randint(jax.random.PRNGKey(6), (B,), 0, S)
    policy.set_policy("baseline")
    rk, rv = jax.jit(common.kv_cache_update)(kc, vc, kn, vn, pos)
    policy.set_policy("opt")
    with mesh:
        ok, ov = jax.jit(common.kv_cache_update)(kc, vc, kn, vn, pos)
    np.testing.assert_array_equal(np.asarray(rk, np.float32),
                                  np.asarray(ok, np.float32))
    np.testing.assert_array_equal(np.asarray(rv, np.float32),
                                  np.asarray(ov, np.float32))
    print("kv_cache_update OK")


def check_train_loss():
    from repro.configs import get_arch, reduced
    from repro.data.pipeline import DataConfig, make_batch
    from repro.models import build

    cfg = reduced(get_arch("granite-moe-1b-a400m"), d_model=64, d_ff=32,
                  vocab=128, n_layers=2, capacity_factor=8.0)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(7))
    batch = make_batch(DataConfig(vocab=cfg.vocab, seq_len=16,
                                  global_batch=8), 0)
    policy.set_policy("baseline")
    ref = float(jax.jit(model.train_loss)(params, batch))
    policy.set_policy("opt")
    with mesh:
        out = float(jax.jit(model.train_loss)(params, batch))
    assert abs(ref - out) < 5e-2 * max(1.0, abs(ref)), (ref, out)
    print(f"train_loss OK ({ref:.4f} vs {out:.4f})")


if __name__ == "__main__":
    check_embed()
    check_moe()
    check_kv_update()
    check_train_loss()
    policy.set_policy("baseline")
    print("POLICY-EQUIV-ALL-OK")
