"""Shared model primitives: norms, RoPE, activations, chunked attention/CE.

Everything is pure-functional JAX over parameter pytrees (no framework).
Attention is implemented *chunked with online softmax* (flash-style) so
activation memory is O(S·chunk) — this is also the numerical reference for
the Pallas flash kernel (kernels/flash_attention/ref.py re-exports it).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# perf-policy sharding pins (§Perf). No-ops without a mesh / with the
# baseline policy, so tests and CPU examples are unaffected.
# ---------------------------------------------------------------------------
def _mesh_axes():
    from jax._src.mesh import thread_resources
    m = thread_resources.env.physical_mesh
    return None if m.empty else m.axis_names


def pin(x, spec_fn):
    """``spec_fn(axis_names) -> PartitionSpec | None``; constrain if active."""
    from repro import policy
    if not policy.current().constrain_activations:
        return x
    axes = _mesh_axes()
    if axes is None:
        return x
    spec = spec_fn(axes)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def _dp(axes):
    return ("pod", "data") if "pod" in axes else "data"


def _axis_size(name) -> int:
    """Product of mesh-axis sizes for a name or tuple of names."""
    from jax._src.mesh import thread_resources
    m = thread_resources.env.physical_mesh
    if m.empty:
        return 1
    names = name if isinstance(name, tuple) else (name,)
    n = 1
    for a in names:
        n *= m.shape[a]
    return n


def pin_batch(x):
    """Activations [B, S, D] → batch over (pod,data), rest unsharded.

    The embedding gather's output sharding is whatever GSPMD salvages from
    the vocab-sharded table (often: replicated). One explicit constraint
    here re-establishes batch parallelism for the entire layer stack.
    """
    P = jax.sharding.PartitionSpec
    return pin(x, lambda ax: P(_dp(ax), *([None] * (x.ndim - 1)))
               if x.shape[0] % _axis_size(_dp(ax)) == 0 else None)


def embed_lookup(embed, tokens):
    """Token-embedding lookup that partitions cleanly at 512 devices.

    Baseline: plain ``embed[tokens]`` — GSPMD handles a gather against a
    vocab-sharded table by replicating it ("involuntary full
    rematerialization"), and the D-sharded variant trips an SPMD bug in the
    gather transpose. Under the opt policy the lookup instead runs inside
    ``shard_map``: every device holds the full vocab for its D-slice, the
    gather is local, and the transpose (scatter-add) is local + one small
    psum over the batch axes — no table replication at any point.
    """
    from repro import policy
    if policy.current().embed_lookup_model_sharded:
        axes = _mesh_axes()
        if axes and "model" in axes \
                and tokens.shape[0] % _axis_size(_dp(axes)) == 0 \
                and embed.shape[1] % _axis_size("model") == 0:
            from jax._src.mesh import thread_resources
            P = jax.sharding.PartitionSpec
            mesh = thread_resources.env.physical_mesh
            dp = _dp(axes)

            def local(emb, tok):
                return emb[tok]              # [B/dp, …, D/model]

            return shard_map(
                local, mesh=mesh,
                in_specs=(P(None, "model"), P(dp, *([None] * (tokens.ndim - 1)))),
                out_specs=P(dp, *([None] * (tokens.ndim - 1)), "model"),
            )(embed, tokens)
    return embed[tokens]


def name_for_remat(x, name: str):
    """Tag a tensor for ``save_only_these_names`` remat policies (§Perf
    iter 5): block outputs ([B,S,D]-sized — as cheap as the carry) are saved
    so the backward recompute skips re-running attention/MoE — including the
    MoE's tensor-parallel psum, which otherwise executes a third time."""
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(x, name)


def kv_cache_update(k_cache, v_cache, k_new, v_new, pos):
    """Decode-step KV write at per-sequence positions (§Perf iter D1).

    Baseline ``cache.at[b, pos].set(new)`` is a batched scatter; when the
    cache sequence axis is sharded, GSPMD rewrites it as a *replicated f32*
    scatter + full-cache convert round trip (~218 GB/step at mixtral-32k).
    Under the opt policy the write runs inside shard_map: the owner shard of
    each position does a local bf16 row update — the NAM one-sided write —
    and every other shard leaves its slab untouched. Zero wire bytes.

    k_cache/v_cache: [B, S, Hkv, Dh]; k_new/v_new: [B, Hkv, Dh]; pos: [B].
    """
    from repro import policy
    axes = _mesh_axes()
    B, S = k_cache.shape[0], k_cache.shape[1]
    if not (policy.current().kv_local_update and axes and "model" in axes
            and B % _axis_size(_dp(axes)) == 0
            and S % _axis_size("model") == 0):
        b = jnp.arange(k_cache.shape[0])
        return (k_cache.at[b, pos].set(k_new.astype(k_cache.dtype)),
                v_cache.at[b, pos].set(v_new.astype(v_cache.dtype)))

    from jax._src.mesh import thread_resources
    P = jax.sharding.PartitionSpec
    mesh = thread_resources.env.physical_mesh
    dp = _dp(axes)

    def body(kc, vc, kn, vn, p):
        Sl = kc.shape[1]
        shard = jax.lax.axis_index("model")
        local = p - shard * Sl                        # position in my slab
        mine = (local >= 0) & (local < Sl)
        safe = jnp.clip(local, 0, Sl - 1)
        bl = jnp.arange(kc.shape[0])
        old_k = kc[bl, safe]
        old_v = vc[bl, safe]
        sel = mine[:, None, None]
        kc = kc.at[bl, safe].set(
            jnp.where(sel, kn.astype(kc.dtype), old_k))
        vc = vc.at[bl, safe].set(
            jnp.where(sel, vn.astype(vc.dtype), old_v))
        return kc, vc

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, "model", None, None), P(dp, "model", None, None),
                  P(dp, None, None), P(dp, None, None), P(dp)),
        out_specs=(P(dp, "model", None, None), P(dp, "model", None, None)),
    )(k_cache, v_cache, k_new, v_new, pos)


def rms_norm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rope(x, positions, theta: float = 1e4):
    """Rotary embedding. x: [..., S, H, D]; positions: [..., S]."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None, None].astype(jnp.float32) \
        * freq[None, None, :]                       # [..., S, 1, half]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def activate(x, kind: str):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "sq_relu":   # nemotron-4: squared ReLU
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(kind)


def softcap(logits, cap: Optional[float]):
    """gemma2 logit soft-capping: cap * tanh(x / cap)."""
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)


def _attend_block(q, k, v, bias, m_prev, l_prev, o_prev, attn_cap):
    """One online-softmax step. q:[B,H,Q,D] k,v:[B,H,C,D] bias:[B,1|H,Q,C]."""
    s = jnp.einsum("bhqd,bhcd->bhqc", q, k).astype(jnp.float32)
    s = softcap(s, attn_cap) + bias
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    o_new = o_prev * corr[..., None] \
        + jnp.einsum("bhqc,bhcd->bhqd", p, v.astype(jnp.float32))
    return m_new, l_new, o_new


def chunked_attention(q, k, v, *, positions_q, positions_k, causal: bool,
                      window: Optional[int] = None,
                      prefix_len=None,
                      attn_cap: Optional[float] = None,
                      chunk: int = 512, scale: Optional[float] = None):
    """Online-softmax attention with GQA, sliding window, prefix-LM masks.

    q: [B, Sq, Hq, D]; k, v: [B, Sk, Hkv, D] (Hq % Hkv == 0 — GQA groups).
    ``window``: sliding-window width (attend to keys within `window` of the
    query position). ``prefix_len``: [B] — keys with pos < prefix_len are
    visible to every query (PaliGemma prefix-LM / Whisper encoder uses
    causal=False instead). Memory: O(Sq·chunk) per head.
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qh = (q * scale).transpose(0, 2, 1, 3)            # [B,Hq,Sq,D]
    kh = k.transpose(0, 2, 1, 3)                      # [B,Hkv,Sk,D]
    vh = v.transpose(0, 2, 1, 3)
    # GQA: fold groups into the batch-of-heads axis of q
    qh = qh.reshape(B, Hkv, g * Sq, D)

    n_chunks = -(-Sk // chunk)
    pad = n_chunks * chunk - Sk
    kh = jnp.pad(kh, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vh = jnp.pad(vh, ((0, 0), (0, 0), (0, pad), (0, 0)))
    pk = jnp.pad(positions_k, ((0, 0), (0, pad)), constant_values=-10 ** 9)
    kh = kh.reshape(B, Hkv, n_chunks, chunk, D)
    vh = vh.reshape(B, Hkv, n_chunks, chunk, D)
    pk = pk.reshape(B, n_chunks, chunk)

    m0 = jnp.full((B, Hkv, g * Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, g * Sq), jnp.float32)
    o0 = jnp.zeros((B, Hkv, g * Sq, D), jnp.float32)

    def body(carry, inputs):
        m, l, o = carry
        kc, vc, pkc = inputs                          # [B,Hkv,chunk,D] ...
        # mask: [B, 1, Sq, chunk] broadcast over head groups
        dq = positions_q[:, None, :, None]            # [B,1,Sq,1]
        dk = pkc[:, None, None, :]                    # [B,1,1,chunk]
        ok = dk > -10 ** 8
        if causal:
            vis = dk <= dq
        else:
            vis = jnp.ones_like(dk <= dq)
        if window is not None:
            vis = vis & (dq - dk < window)
        if prefix_len is not None:
            vis = vis | (dk < prefix_len[:, None, None, None])
        bias = jnp.where(vis & ok, 0.0, NEG_INF).astype(jnp.float32)
        bias = jnp.broadcast_to(bias, (B, 1, Sq, chunk))
        bias = jnp.broadcast_to(bias[:, :, None], (B, 1, g, Sq, chunk)) \
            .reshape(B, 1, g * Sq, chunk)
        m, l, o = _attend_block(qh, kc, vc, bias, m, l, o, attn_cap)
        return (m, l, o), None

    (m, l, o), _ = jax.lax.scan(
        body, (m0, l0, o0),
        (kh.transpose(2, 0, 1, 3, 4), vh.transpose(2, 0, 1, 3, 4),
         pk.transpose(1, 0, 2)))
    o = o / jnp.maximum(l[..., None], 1e-30)
    o = o.reshape(B, Hkv, g, Sq, D).reshape(B, Hq, Sq, D)
    return o.transpose(0, 2, 1, 3).astype(q.dtype)    # [B,Sq,Hq,D]


def decode_attention(q, k_cache, v_cache, kv_len, *, window=None,
                     attn_cap=None, scale=None, sink_len: int = 0):
    """Single-token decode attention over a (possibly sharded) KV cache.

    q: [B, Hq, D]; k_cache/v_cache: [B, S, Hkv, D]; kv_len: [B] valid length.
    Returns [B, Hq, D]. Window masking keeps only the trailing ``window``
    positions (plus ``sink_len`` leading sink tokens when set).
    """
    B, Hq, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qh = (q * scale).reshape(B, Hkv, g, D)
    pos = jnp.arange(S)[None, :]                      # [1,S]
    vis = pos < kv_len[:, None]
    if window is not None:
        in_win = pos >= (kv_len[:, None] - window)
        if sink_len:
            in_win = in_win | (pos < sink_len)
        vis = vis & in_win
    s = jnp.einsum("bkgd,bskd->bkgs", qh, k_cache).astype(jnp.float32)
    s = softcap(s, attn_cap)
    s = jnp.where(vis[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(q.dtype), v_cache)
    return o.reshape(B, Hq, D)


def chunked_cross_entropy(hidden, emb, targets, mask, *, chunk: int = 1024,
                          logit_cap: Optional[float] = None):
    """Cross-entropy without materializing [B,S,V] logits.

    hidden: [B, S, D]; emb: [V, D] (tied head); targets: [B, S] int32;
    mask: [B, S]. Scans over sequence chunks; per-chunk logits [B,chunk,V].
    Returns (mean_loss, total_weight).
    """
    B, S, D = hidden.shape
    V = emb.shape[0]
    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    h = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
    t = jnp.pad(targets, ((0, 0), (0, pad)))
    m = jnp.pad(mask, ((0, 0), (0, pad)))
    h = h.reshape(B, n_chunks, chunk, D).transpose(1, 0, 2, 3)
    t = t.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
    m = m.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    from repro import policy
    P = jax.sharding.PartitionSpec
    vocab_sharded = policy.current().ce_vocab_sharded \
        and _mesh_axes() is not None and "model" in (_mesh_axes() or ())
    if vocab_sharded:
        # reshard the tied head ONCE per step: vocab→model. Each chunk's
        # logits [B,chunk,V] then shard over V; the only cross-device work
        # per chunk is the [B,chunk]-sized lse/gold reductions, instead of
        # a [B,chunk,V]-sized partial-sum all-reduce.
        emb = jax.lax.with_sharding_constraint(emb, P("model", None))

    def body(carry, inputs):
        loss_sum, w_sum = carry
        hc, tc, mc = inputs
        logits = jnp.einsum("bsd,vd->bsv", hc, emb).astype(jnp.float32)
        if vocab_sharded:
            logits = jax.lax.with_sharding_constraint(
                logits, P(_dp(_mesh_axes()), None, "model"))
        logits = softcap(logits, logit_cap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mc
        return (loss_sum + jnp.sum(nll), w_sum + jnp.sum(mc)), None

    (loss_sum, w_sum), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (h, t, m))
    return loss_sum / jnp.maximum(w_sum, 1.0), w_sum
