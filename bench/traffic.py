"""The benchmark's own copy of the TPC-C traffic generator, and its guard.

The engine's driver draws its transactions inside the program
(``workload.gen_mixed``), so the benchmark cannot hand it inputs. This file
keeps a copy of that generator, draw for draw, under the benchmark's own
paths. It has two uses:

* the plain reference replays the run from the copy's draws, never from
  what the program drew;
* ``guard`` compares the program's draws with the copy's for the first
  rounds of a call before the measured window, so a change to the
  program's generator fails the run instead of silently changing the
  traffic.

A traffic mix is a data file under ``bench/traffic/``; its keys are the
parameters below (``Mix``). The copy runs op by op, as the driver does, so
the two draw with the same compiled operations.
"""
from __future__ import annotations

import json
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

# canonical type order: the integer id of a transaction type
TXN_TYPES = ("neworder", "payment", "orderstatus", "delivery", "stocklevel")
MAX_OL = 15
DISTRICTS = 10


class Mix(NamedTuple):
    """One traffic mix, as its data file states it."""
    name: str
    lanes_per_warehouse: int      # closed-loop clients (vector lanes)
    mix: dict                     # type -> share of fresh draws
    dist_degree: float            # % of new-orders with remote lines
    remote_payment_frac: float    # share of payments to a remote customer
    warmup_rounds: int            # rounds of the driver call before the
    #                               window
    stock_last_n: int             # orders a stock-level scans
    locality_mode: Optional[str]  # the driver's locality accounting


def load_mix(path: str) -> Mix:
    with open(path) as f:
        d = json.load(f)
    mix = {t: float(d["mix"][t]) for t in TXN_TYPES}
    if abs(sum(mix.values()) - 1.0) > 1e-9:
        raise ValueError(f"{path}: the mix shares sum to {sum(mix.values())}")
    # a call's first round meets fresh draws, its later rounds merged
    # retries: on a mesh each is a program of its own, and the second
    # round's merge a program of its own too
    if int(d["warmup_rounds"]) < 3:
        raise ValueError(f"{path}: fewer than 3 warm-up rounds leave "
                         f"programs to compile inside the window")
    return Mix(name=d["name"], lanes_per_warehouse=int(
        d["lanes_per_warehouse"]), mix=mix,
        dist_degree=float(d["dist_degree"]),
        remote_payment_frac=float(d["remote_payment_frac"]),
        warmup_rounds=int(d["warmup_rounds"]),
        stock_last_n=int(d["stock_last_n"]),
        locality_mode=d.get("locality_mode"))


def _mix_logits(mix):
    p = jnp.asarray([float(mix.get(t, 0.0)) for t in TXN_TYPES], jnp.float32)
    return jnp.log(jnp.maximum(p, 1e-30))


def _neworder(key, n, n_w, n_items, n_c, dist_degree, item_logits):
    ks = jax.random.split(key, 8)
    w_id = jax.random.randint(ks[0], (n,), 0, n_w)
    d_id = jax.random.randint(ks[1], (n,), 0, DISTRICTS)
    c_id = jax.random.randint(ks[2], (n,), 0, n_c)
    ol_cnt = jax.random.randint(ks[3], (n,), 5, MAX_OL + 1)
    gumbel = jax.random.gumbel(ks[4], (n, item_logits.shape[0]))
    _, item_ids = jax.lax.top_k(item_logits[None, :] + gumbel, MAX_OL)
    item_ids = item_ids.astype(jnp.int32)
    is_dist = jax.random.uniform(ks[5], (n,)) < dist_degree / 100.0
    remote_w = jax.random.randint(ks[6], (n, MAX_OL), 0,
                                  jnp.maximum(n_w - 1, 1))
    remote_w = jnp.where(remote_w >= w_id[:, None], remote_w + 1, remote_w)
    remote_w = jnp.clip(remote_w, 0, n_w - 1)
    line_remote = jax.random.uniform(ks[7], (n, MAX_OL)) < 0.5
    line_remote = line_remote.at[:, 0].set(True)
    is_remote = is_dist[:, None] & line_remote & (n_w > 1)
    supply_w = jnp.where(is_remote, remote_w, w_id[:, None])
    qty = jax.random.randint(ks[3], (n, MAX_OL), 1, 11)
    return dict(w_id=w_id.astype(jnp.int32), d_id=d_id, c_id=c_id,
                ol_cnt=ol_cnt, item_ids=item_ids,
                supply_w=supply_w.astype(jnp.int32), qty=qty,
                is_remote=is_remote)


def _payment(key, n, n_w, n_c, remote_frac):
    ks = jax.random.split(key, 5)
    w_id = jax.random.randint(ks[0], (n,), 0, n_w)
    d_id = jax.random.randint(ks[1], (n,), 0, DISTRICTS)
    c_id = jax.random.randint(ks[2], (n,), 0, n_c)
    remote = (jax.random.uniform(ks[3], (n,)) < remote_frac) & (n_w > 1)
    rw = jax.random.randint(ks[3], (n,), 0, jnp.maximum(n_w - 1, 1))
    rw = jnp.where(rw >= w_id, rw + 1, rw)
    c_w_id = jnp.where(remote, jnp.clip(rw, 0, n_w - 1), w_id)
    amount = jax.random.randint(ks[4], (n,), 100, 500000)
    return dict(w_id=w_id.astype(jnp.int32), d_id=d_id, c_id=c_id,
                c_w_id=c_w_id.astype(jnp.int32), amount=amount)


def _three(key, n, n_w, hi, lo_last, hi_last, last):
    ks = jax.random.split(key, 3)
    w_id = jax.random.randint(ks[0], (n,), 0, n_w)
    return {"w_id": w_id.astype(jnp.int32),
            "d_id": jax.random.randint(ks[1], (n,), 0, hi),
            last: jax.random.randint(ks[2], (n,), lo_last, hi_last)}


def draw(key, traffic: Mix, n_lanes: int, n_w: int, n_items: int,
         n_c: int) -> dict:
    """One round of the mix for every lane: ``{"txn_type": [T], type:
    {field: array}}``, drawn as the engine's generator draws it."""
    kt, kn, kp, ko, kd, ks_ = jax.random.split(key, 6)
    item_logits = jnp.zeros((n_items,), jnp.float32)
    return {
        "txn_type": jax.random.categorical(
            kt, _mix_logits(traffic.mix), shape=(n_lanes,)).astype(
                jnp.int32),
        "neworder": _neworder(kn, n_lanes, n_w, n_items, n_c,
                              traffic.dist_degree, item_logits),
        "payment": _payment(kp, n_lanes, n_w, n_c,
                            traffic.remote_payment_frac),
        "orderstatus": _three(ko, n_lanes, n_w, DISTRICTS, 0, n_c, "c_id"),
        "delivery": _three(kd, n_lanes, n_w, DISTRICTS, 1, 11, "carrier"),
        "stocklevel": _three(ks_, n_lanes, n_w, DISTRICTS, 10, 21,
                             "threshold"),
    }


def round_keys(key, n_rounds: int):
    """The per-round keys of one driver call: ``key, sub = split(key)``."""
    subs = []
    for _ in range(n_rounds):
        key, sub = jax.random.split(key)
        subs.append(sub)
    return subs


def to_host(rnd: dict) -> dict:
    return jax.tree.map(np.asarray, jax.device_get(rnd))


def guard(program_draws, copy_draws) -> None:
    """Raise unless every field of the program's draws equals the copy's,
    bit for bit. Both are ``{"txn_type": …, type: {field: …}}``."""
    a, b = to_host(program_draws), to_host(copy_draws)
    if a.keys() != b.keys():
        raise RuntimeError(f"traffic guard: the program draws "
                           f"{sorted(a)} where the copy draws {sorted(b)}")
    for t in a:
        fa = a[t] if isinstance(a[t], dict) else {"": a[t]}
        fb = b[t] if isinstance(b[t], dict) else {"": b[t]}
        if fa.keys() != fb.keys():
            raise RuntimeError(f"traffic guard: {t} has fields {sorted(fa)}"
                               f" in the program, {sorted(fb)} in the copy")
        for f in fa:
            x, y = fa[f], fb[f]
            if x.shape != y.shape or x.dtype != y.dtype \
                    or not np.array_equal(x, y):
                raise RuntimeError(
                    f"traffic guard: the program's generator draws another "
                    f"{t}.{f} than the benchmark's copy; the traffic has "
                    f"changed")
