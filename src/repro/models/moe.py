"""Mixture-of-Experts layer: top-k router + capacity-based grouped dispatch.

Dispatch is sort-free (rank-within-expert via masked cumsum) and
capacity-bounded, so FLOPs are k·T·capacity_factor · (expert FFN) — NOT
E·T — which keeps the roofline honest. The expert matmul is a grouped GEMM
[E, C, D] × [E, D, F]; its Pallas kernel lives in kernels/moe_gmm. Expert
weights shard over the ``model``/``expert`` mesh axis (EP); the
gather/scatter between token-sharded and expert-sharded layouts lowers to the
all-to-all pair classic expert parallelism uses.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map


def init_moe(key, d_model: int, d_ff: int, n_experts: int, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in = d_model ** -0.5
    s_out = d_ff ** -0.5
    return {
        "router": (jax.random.normal(k1, (d_model, n_experts)) * s_in
                   ).astype(jnp.float32),
        "w_gate": (jax.random.normal(k2, (n_experts, d_model, d_ff)) * s_in
                   ).astype(dtype),
        "w_in": (jax.random.normal(k3, (n_experts, d_model, d_ff)) * s_in
                 ).astype(dtype),
        "w_out": (jax.random.normal(k4, (n_experts, d_ff, d_model)) * s_out
                  ).astype(dtype),
    }


class MoEStats(NamedTuple):
    dropped_fraction: jnp.ndarray   # tokens over capacity
    load: jnp.ndarray               # [E] tokens per expert
    aux_loss: jnp.ndarray           # load-balancing loss (Switch-style)


def apply_moe(params, x, *, top_k: int, capacity_factor: float = 1.25,
              activation=jax.nn.silu):
    """x: [T, D] (already flattened). Returns (y [T, D], MoEStats).

    Under the opt PerfPolicy (and a live mesh) this dispatches to
    :func:`apply_moe_sharded` — routing/dispatch run *locally per data
    shard* inside ``shard_map`` with TP over ``model`` as one explicit psum.
    The global formulation below is the GSPMD baseline; its cross-token
    cumsum + scatter chain is unpartitionable and replicates (§Perf iter 2).
    """
    from repro import policy
    from repro.models.common import _axis_size, _dp, _mesh_axes
    axes = _mesh_axes()
    T, D = x.shape
    F = params["w_in"].shape[2]
    if policy.current().constrain_activations and axes \
            and "model" in axes and "data" in axes \
            and T % _axis_size(_dp(axes)) == 0 \
            and F % _axis_size("model") == 0 \
            and D % _axis_size("model") == 0:
        return apply_moe_sharded(params, x, top_k=top_k,
                                 capacity_factor=capacity_factor,
                                 activation=activation)
    return _apply_moe_global(params, x, top_k=top_k,
                             capacity_factor=capacity_factor,
                             activation=activation)


def _apply_moe_global(params, x, *, top_k: int, capacity_factor: float = 1.25,
                      activation=jax.nn.silu):
    T, D = x.shape
    E = params["router"].shape[1]
    F = params["w_in"].shape[2]
    logits = (x.astype(jnp.float32) @ params["router"])       # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)       # [T, k]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    C = max(1, int(capacity_factor * T * top_k / E))
    # rank of each (token, choice) within its expert, in token order — the
    # deterministic arbitration NIC-style tournament, reused from core/cas.py
    flat_e = expert_idx.reshape(-1)                           # [T*k]
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)       # [T*k, E]
    rank = jnp.cumsum(onehot, axis=0) - onehot                # prior count
    my_rank = jnp.take_along_axis(rank, flat_e[:, None], axis=1)[:, 0]
    keep = my_rank < C
    load = jnp.sum(onehot, axis=0)

    # scatter tokens into [E, C, D] buckets (dropped → OOB, mode='drop')
    tok_of_flat = jnp.repeat(jnp.arange(T), top_k)
    e_idx = jnp.where(keep, flat_e, E)
    c_idx = jnp.where(keep, my_rank, 0)
    buckets = jnp.zeros((E + 1, C, D), x.dtype)
    buckets = buckets.at[e_idx, c_idx].set(x[tok_of_flat], mode="drop")
    buckets = buckets[:E]

    # grouped expert FFN (the Pallas moe_gmm kernel computes this on TPU)
    g = jnp.einsum("ecd,edf->ecf", buckets, params["w_gate"])
    h = jnp.einsum("ecd,edf->ecf", buckets, params["w_in"])
    h = activation(g) * h
    out = jnp.einsum("ecf,efd->ecd", h, params["w_out"])      # [E, C, D]

    # combine back, weighted by the (renormalized) gates
    y = jnp.zeros((T, D), jnp.float32)
    contrib = out[jnp.where(keep, flat_e, 0), c_idx]          # [T*k, D]
    w = jnp.where(keep, gate_vals.reshape(-1), 0.0)
    y = y.at[tok_of_flat].add(contrib.astype(jnp.float32) * w[:, None])

    # Switch-style load-balancing auxiliary loss
    me = jnp.mean(probs, axis=0)
    ce = load.astype(jnp.float32) / jnp.maximum(jnp.sum(load), 1)
    aux = E * jnp.sum(me * ce)
    stats = MoEStats(
        dropped_fraction=1.0 - jnp.sum(keep) / (T * top_k),
        load=load, aux_loss=aux)
    return y.astype(x.dtype), stats


def apply_moe_sharded(params, x, *, top_k: int, capacity_factor: float,
                      activation=jax.nn.silu):
    """Expert MLP under shard_map: data-local dispatch + one model psum.

    Layout (mesh axes (…,"data","model"), dp = ("pod","data") if present):
      x        [T, D]        tokens over dp, D full      (in_spec)
      router   [D, E]        replicated
      w_gate/in[E, D, F]     F over model (FSDP storage over data is
                             all-gathered at the boundary — weights enter
                             fully for the expert dims)
      w_out    [E, F, D]     F over model
    Per shard: route OWN tokens with local capacity C/|dp| (statistically
    identical load bound), grouped-GEMM them, psum the second GEMM's
    F-partial over "model", combine locally. No global cumsum, no
    replicated scatter — the GSPMD baseline's two pathologies.
    """
    from jax._src.mesh import thread_resources
    from repro.models.common import _dp, _mesh_axes
    P = jax.sharding.PartitionSpec
    mesh = thread_resources.env.physical_mesh
    dp = _dp(_mesh_axes())

    def body(router, w_gate, w_in, w_out, xl):
        Tl, D = xl.shape
        E = router.shape[1]
        logits = xl.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, top_k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        C = max(1, int(capacity_factor * Tl * top_k / E))
        flat_e = expert_idx.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        rank = jnp.cumsum(onehot, axis=0) - onehot
        my_rank = jnp.take_along_axis(rank, flat_e[:, None], axis=1)[:, 0]
        keep = my_rank < C
        load = jnp.sum(onehot, axis=0)

        tok_of_flat = jnp.repeat(jnp.arange(Tl), top_k)
        e_idx = jnp.where(keep, flat_e, E)
        c_idx = jnp.where(keep, my_rank, 0)
        buckets = jnp.zeros((E + 1, C, D), xl.dtype)
        buckets = buckets.at[e_idx, c_idx].set(xl[tok_of_flat], mode="drop")
        buckets = buckets[:E]

        g = jnp.einsum("ecd,edf->ecf", buckets, w_gate)   # F/model local
        h = jnp.einsum("ecd,edf->ecf", buckets, w_in)
        h = activation(g) * h
        out = jnp.einsum("ecf,efd->ecd", h, w_out)        # partial over F
        # §Perf iter 6: E·C ≈ k·cf·Tl > Tl, so reduce the [E,C,D] partial
        # with a *scatter* over D, combine on D-shards, and all-gather the
        # carry-sized y — ~1.4x fewer wire bytes than psum([E,C,D]) and the
        # combine gathers move D/|model| slices instead of full rows.
        nm = jax.lax.axis_size("model")
        out = jax.lax.psum_scatter(out.astype(xl.dtype), "model",
                                   scatter_dimension=2, tiled=True)
        yl = jnp.zeros((Tl, D // nm), jnp.float32)        # local D slice
        contrib = out[jnp.where(keep, flat_e, 0), c_idx]
        w = jnp.where(keep, gate_vals.reshape(-1), 0.0)
        yl = yl.at[tok_of_flat].add(
            contrib.astype(jnp.float32) * w[:, None])
        y = jax.lax.all_gather(yl.astype(xl.dtype), "model", axis=1,
                               tiled=True)                # [Tl, D]

        gload = jax.lax.psum(load, dp)
        me = jax.lax.psum(jnp.sum(probs, axis=0), dp) \
            / jax.lax.psum(jnp.asarray(Tl, jnp.float32), dp)
        ce = gload.astype(jnp.float32) / jnp.maximum(jnp.sum(gload), 1)
        aux = E * jnp.sum(me * ce)
        kept = jax.lax.psum(jnp.sum(keep), dp)
        total = jax.lax.psum(jnp.asarray(Tl * top_k), dp)
        stats = MoEStats(dropped_fraction=1.0 - kept / total,
                         load=gload, aux_loss=aux)
        return y.astype(xl.dtype), stats

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P(None, None, "model"),
                  P(None, None, "model"), P(None, "model", None),
                  P(dp, None)),
        out_specs=(P(dp, None),
                   MoEStats(dropped_fraction=P(), load=P(), aux_loss=P())),
        # replication of y over "model" comes from the tiled all_gather,
        # which the static VMA checker can't see through
        check_vma=False,
    )(params["router"], params["w_gate"], params["w_in"], params["w_out"], x)
