"""Plain reference of the TPC-C engine's semantics, in NumPy on the host.

It imports nothing of the program. From a configuration, a traffic mix and
the seed it replays the driver's closed loop: the copy in ``traffic.py``
draws each round, aborted transactions re-enter the next round with their
own type and inputs, and each round runs the five transaction types one
after another (new-order, payment, delivery, order-status, stock-level)
over the lanes that drew them. It states the engine's rules plainly:

* snapshot isolation over multi-version records: every transaction of a
  sub-round reads the committed state before the sub-round; a record is
  granted to the lowest lane that writes it (first committer wins, by lane
  order), and a lane commits only if it is granted every record it writes
  and each record's next old-version slot has been moved out;
* a committed write moves the current version into the record's ring of
  ``n_old`` old versions and installs ⟨lane, commit timestamp⟩, the lane's
  last timestamp plus one; every committed transaction of a write type
  publishes its timestamp in the lane's slot of the timestamp vector;
* inserts (orders, order lines, new-orders, history) go to the lane's own
  extend at its cursor, and every inserted order is in the order index;
* after every round the version mover copies each record's oldest
  unmoved old version into its ring of ``n_overflow`` overflow versions.

The order index holds every order inserted: TPC-C's delivery, order-status
and stock-level find orders by it.

``break_si=True`` is the control: it grants every write, so concurrent
writers of one record all commit and the later lane's install overwrites
the earlier one's (lost updates). It breaks the isolation the
configuration states, and a run compared against it must read as wrong.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import numpy as np

import traffic as traffic_mod

WIDTH = 8
DISTRICTS = 10
MAX_OL = 15
MAX_O_PER_DISTRICT = 1 << 14
LOCKED, DELETED, MOVED = 1, 2, 4
TYPES = traffic_mod.TXN_TYPES
# payload columns
W_YTD = 1
D_YTD, D_NEXT_O, D_NEXT_DELIV = 1, 2, 3
C_BAL, C_YTD, C_PAYCNT, C_DELCNT = 0, 1, 2, 3
S_QTY, S_YTD, S_ORDCNT, S_REMCNT = 0, 1, 2, 3
I_PRICE = 0
O_CID, O_CARRIER, O_OLCNT, O_ENTRY, O_OID, O_DKEY = 0, 1, 2, 3, 4, 5
OL_IID, OL_SUPW, OL_QTY, OL_AMOUNT, OL_DELD = 0, 1, 2, 3, 4


class Layout(NamedTuple):
    """Record slots of the tables, laid out back to back in this order."""
    W: int
    I: int
    C: int
    T: int
    opt: int
    w_base: int
    d_base: int
    c_base: int
    s_base: int
    i_base: int
    o_base: int
    ol_base: int
    no_base: int
    h_base: int
    n_records: int

    @staticmethod
    def make(W, I, C, T, opt):
        counts = [W, W * DISTRICTS, W * DISTRICTS * C, W * I, I, T * opt,
                  T * opt * MAX_OL, T * opt, T * opt]
        bases = np.concatenate([[0], np.cumsum(counts)]).tolist()
        return Layout(W, I, C, T, opt, *bases)

    def d(self, w, d):
        return self.d_base + w * DISTRICTS + d

    def c(self, w, d, c):
        return self.c_base + (w * DISTRICTS + d) * self.C + c

    def s(self, w, i):
        return self.s_base + w * self.I + i


def order_key(w, d, o_id):
    return (w * DISTRICTS + d) * MAX_O_PER_DISTRICT + o_id


class State:
    """The whole store, as the configuration describes it."""

    def __init__(self, lay: Layout, n_old: int, n_ovf: int, load: dict):
        R, T = lay.n_records, lay.T
        self.lay, self.K, self.KO = lay, n_old, n_ovf
        self.cur_hdr = np.zeros((R, 2), np.uint32)
        self.cur_data = np.zeros((R, WIDTH), np.int32)
        self.old_hdr = np.zeros((R, n_old, 2), np.uint32)
        self.old_hdr[:, :, 0] = MOVED
        self.old_data = np.zeros((R, n_old, WIDTH), np.int32)
        self.next_write = np.zeros((R,), np.int32)
        self.ovf_hdr = np.zeros((R, n_ovf, 2), np.uint32)
        self.ovf_hdr[:, :, 0] = DELETED
        self.ovf_data = np.zeros((R, n_ovf, WIDTH), np.int32)
        self.ovf_next = np.zeros((R,), np.int32)
        self.cur_hdr[lay.o_base:, 0] = DELETED   # the insert extends
        W, I = lay.W, lay.I
        self.cur_data[lay.w_base:lay.w_base + W, 0] = load["w_tax"]
        self.cur_data[lay.d_base:lay.d_base + W * DISTRICTS, 0] = \
            load["d_tax"]
        self.cur_data[lay.i_base:lay.i_base + I, I_PRICE] = load["price"]
        self.cur_data[lay.s_base:lay.s_base + W * I, S_QTY] = load["s_qty"]
        self.vec = np.zeros((T,), np.uint32)
        self.cursor = np.zeros((T,), np.int32)
        self.hist_cursor = np.zeros((T,), np.int32)
        self.index = {}                    # order key -> order slot
        self.unmoved = np.zeros((0,), np.int64)   # records the mover owes

    # ---- versions ---------------------------------------------------------
    def install(self, slots, tids, cts, data):
        """Install new current versions at pairwise distinct slots whose
        next old-version slot is free (the commit rule checked it)."""
        wpos = self.next_write[slots] % self.K
        free = (self.old_hdr[slots, wpos, 0] & MOVED) != 0
        if not free.all():
            raise AssertionError("install into an unmoved old slot")
        self.old_hdr[slots, wpos, 0] = self.cur_hdr[slots, 0] \
            & ~np.uint32(LOCKED | MOVED)
        self.old_hdr[slots, wpos, 1] = self.cur_hdr[slots, 1]
        self.old_data[slots, wpos] = self.cur_data[slots]
        self.cur_hdr[slots, 0] = tids.astype(np.uint32) << np.uint32(3)
        self.cur_hdr[slots, 1] = cts
        self.cur_data[slots] = data
        self.next_write[slots] += 1
        self.unmoved = np.union1d(self.unmoved, slots)

    def move_versions(self):
        """One sweep of the version mover over the records that owe one."""
        r = self.unmoved
        if r.size == 0:
            return
        K, KO = self.K, self.KO
        pos = (self.next_write[r, None] + np.arange(K)[None, :]) % K
        hdr = np.take_along_axis(self.old_hdr[r, :, 0], pos, axis=1)
        not_moved = (hdr & MOVED) == 0
        has = not_moved.any(axis=1)
        r, pos, not_moved = r[has], pos[has], not_moved[has]
        src = pos[np.arange(r.size), np.argmax(not_moved, axis=1)]
        opos = self.ovf_next[r] % KO
        self.ovf_hdr[r, opos, 0] = self.old_hdr[r, src, 0] \
            & ~np.uint32(DELETED)
        self.ovf_hdr[r, opos, 1] = self.old_hdr[r, src, 1]
        self.ovf_data[r, opos] = self.old_data[r, src]
        self.ovf_next[r] = (self.ovf_next[r] + 1) % KO
        self.old_hdr[r, src, 0] |= np.uint32(MOVED)
        still = ((self.old_hdr[r, :, 0] & MOVED) == 0).any(axis=1)
        self.unmoved = r[still]

    # ---- the commit rule ----------------------------------------------------
    def commit(self, lanes, slots, mask, data, break_si=False):
        """Validate, lock and install the write sets of one sub-round.

        ``lanes`` [n] are the lanes running it, ``slots``/``mask``
        [n, WS] their write sets, ``data`` [n, WS, WIDTH] the new payloads.
        Returns the per-lane commit decision."""
        n, WS = slots.shape
        req_lane = np.repeat(lanes, WS)[mask.reshape(-1)]
        req_slot = slots.reshape(-1)[mask.reshape(-1)]
        req_row = np.repeat(np.arange(n), WS)[mask.reshape(-1)]
        if break_si:
            won = np.ones(req_slot.shape, bool)
        else:
            first = {}
            for s, t in zip(req_slot.tolist(), req_lane.tolist()):
                if first.get(s, t) >= t:
                    first[s] = t
            won = np.fromiter((first[s] == t for s, t in zip(
                req_slot.tolist(), req_lane.tolist())), bool,
                count=req_slot.size)
        wpos = self.next_write[req_slot] % self.K
        ok = won & ((self.old_hdr[req_slot, wpos, 0] & MOVED) != 0)
        fails = np.zeros((n,), np.int64)
        np.add.at(fails, req_row, (~ok).astype(np.int64))
        committed = fails == 0
        cts = self.vec[lanes] + np.uint32(1)
        inst = committed[req_row]
        if inst.any():
            rows, cols = np.nonzero(mask)
            new = data[rows, cols][inst]
            s, l, c = req_slot[inst], req_lane[inst], cts[req_row[inst]]
            if break_si:   # later lanes overwrite earlier ones
                order = np.argsort(l, kind="stable")
                s, l, c, new = s[order], l[order], c[order], new[order]
                for i in range(s.size):
                    self._install_any(s[i:i + 1], l[i:i + 1], c[i:i + 1],
                                      new[i:i + 1])
            else:
                self.install(s, l, c, new)
        self.vec[lanes[committed]] = cts[committed]
        return committed

    def _install_any(self, slots, tids, cts, data):
        wpos = self.next_write[slots] % self.K
        if (self.old_hdr[slots, wpos, 0] & MOVED).all():
            self.install(slots, tids, cts, data)

    # ---- reads --------------------------------------------------------------
    def latest_order(self, w, d):
        """Slot of the newest indexed order of (w, d), or -1."""
        best = -1
        for o in range(self.cur_data[self.lay.d(w, d), D_NEXT_O] - 1, -1, -1):
            s = self.index.get(order_key(w, d, o))
            if s is not None:
                best = s
                break
        return best


def load_data(seed_key, W: int, I: int) -> dict:
    """The initial table contents the configuration loads from the seed:
    warehouse and district taxes, item prices, stock quantities."""
    ks = jax.random.split(seed_key, 6)
    return {k: np.asarray(jax.device_get(v)) for k, v in {
        "w_tax": jax.random.randint(ks[0], (W,), 0, 2000),
        "d_tax": jax.random.randint(ks[1], (W * DISTRICTS,), 0, 2000),
        "price": jax.random.randint(ks[2], (I,), 100, 10000),
        "s_qty": jax.random.randint(ks[3], (W * I,), 10, 101)}.items()}


class Outcome(NamedTuple):
    """What one driver call reports: its statistics and the answers of its
    read-only transactions, one ``(result, found)`` per sub-round run."""
    attempts: dict
    commits: dict
    retries: dict
    snapshot_misses: dict
    contention_aborts: dict
    ovf_reads: dict
    delivered: int
    ovf_peak: int
    answers: dict


def run_call(st: State, rounds, stock_last_n: int,
             break_si=False) -> Outcome:
    """Replay one call of the driver: ``rounds`` are the host draws of each
    round (``traffic.draw``); the retry queue starts empty."""
    lay, T = st.lay, st.lay.T
    att = {t: 0 for t in TYPES}
    com = {t: 0 for t in TYPES}
    ret = {t: 0 for t in TYPES}
    zero = {t: 0 for t in TYPES}
    answers = {"orderstatus": [], "stocklevel": []}
    delivered = ovf_peak = 0
    pending, pending_type = None, np.full((T,), -1, np.int32)
    for r, fresh in enumerate(rounds):
        inp = fresh if pending is None else jax.tree.map(
            lambda p, f: np.where(
                (pending_type >= 0).reshape((T,) + (1,) * (f.ndim - 1)),
                p, f), pending, fresh)
        ttype = inp["txn_type"]
        aborted = np.zeros((T,), bool)
        for name, fn in (("neworder", _neworder), ("payment", _payment),
                         ("delivery", _delivery)):
            lanes = np.nonzero(ttype == TYPES.index(name))[0]
            if lanes.size == 0:
                continue
            sub = {k: v[lanes] for k, v in inp[name].items()}
            committed, n_del = fn(st, lanes, sub, r, break_si)
            att[name] += lanes.size
            com[name] += int(committed.sum())
            ret[name] += int((~committed).sum())
            aborted[lanes[~committed]] = True
            delivered += n_del
        for name, fn in (("orderstatus", _orderstatus),
                         ("stocklevel", _stocklevel)):
            lanes = np.nonzero(ttype == TYPES.index(name))[0]
            if lanes.size == 0:
                continue
            sub = {k: v[lanes] for k, v in inp[name].items()}
            answers[name].append((lanes, *fn(st, sub, stock_last_n)))
            att[name] += lanes.size
            com[name] += lanes.size
        pending_type = np.where(aborted, ttype, -1).astype(np.int32)
        pending = inp
        st.move_versions()
        ovf_peak = max(ovf_peak, int(st.ovf_next.max()))
    for i, t in enumerate(TYPES):
        ret[t] -= int((pending_type == i).sum())
    contention = {t: att[t] - com[t] for t in TYPES}
    return Outcome(att, com, ret, dict(zero), contention, dict(zero),
                   delivered, ovf_peak, answers)


def _neworder(st: State, lanes, inp, round_no, break_si):
    lay = st.lay
    n = lanes.size
    w, d, c = inp["w_id"], inp["d_id"], inp["c_id"]
    lines = np.arange(MAX_OL)[None, :] < inp["ol_cnt"][:, None]
    dsl = lay.d(w, d)
    ssl = lay.s(inp["supply_w"], inp["item_ids"])
    dist = st.cur_data[dsl].copy()
    o_id = dist[:, D_NEXT_O].copy()
    dist[:, D_NEXT_O] += 1
    stock = st.cur_data[ssl].copy()                       # [n, 15, W]
    q = stock[:, :, S_QTY] - inp["qty"]
    stock[:, :, S_QTY] = np.where(q >= 10, q, q + 91)
    stock[:, :, S_YTD] += inp["qty"]
    stock[:, :, S_ORDCNT] += 1
    stock[:, :, S_REMCNT] += inp["is_remote"].astype(np.int32)
    slots = np.concatenate([dsl[:, None], ssl], axis=1)
    mask = np.concatenate([np.ones((n, 1), bool), lines], axis=1)
    data = np.concatenate([dist[:, None], stock], axis=1)
    price = st.cur_data[lay.i_base + inp["item_ids"], I_PRICE]
    committed = st.commit(lanes, slots, mask, data, break_si)
    ins = committed & (st.cursor[lanes] < lay.opt)
    if ins.any():
        tl, cts = lanes[ins], st.vec[lanes[ins]]
        local = st.cursor[tl]
        oslot = lay.o_base + tl * lay.opt + local
        odata = np.zeros((tl.size, WIDTH), np.int32)
        odata[:, O_CID] = c[ins]
        odata[:, O_CARRIER] = -1
        odata[:, O_OLCNT] = inp["ol_cnt"][ins]
        odata[:, O_ENTRY] = round_no
        odata[:, O_OID] = o_id[ins]
        odata[:, O_DKEY] = w[ins] * DISTRICTS + d[ins]
        st.install(oslot, tl, cts, odata)
        nodata = np.zeros((tl.size, WIDTH), np.int32)
        nodata[:, 0] = o_id[ins]
        nodata[:, 1] = w[ins] * DISTRICTS + d[ins]
        st.install(lay.no_base + tl * lay.opt + local, tl, cts, nodata)
        oldata = np.zeros((tl.size, MAX_OL, WIDTH), np.int32)
        oldata[:, :, OL_IID] = inp["item_ids"][ins]
        oldata[:, :, OL_SUPW] = inp["supply_w"][ins]
        oldata[:, :, OL_QTY] = inp["qty"][ins]
        oldata[:, :, OL_AMOUNT] = price[ins] * inp["qty"][ins]
        oldata[:, :, OL_DELD] = -1
        olsl = lay.ol_base + (oslot - lay.o_base)[:, None] * MAX_OL \
            + np.arange(MAX_OL)[None, :]
        lm = lines[ins]
        st.install(olsl[lm], np.broadcast_to(tl[:, None], lm.shape)[lm],
                   np.broadcast_to(cts[:, None], lm.shape)[lm], oldata[lm])
        for k, s in zip(order_key(w[ins], d[ins], o_id[ins]).tolist(),
                        oslot.tolist()):
            st.index[k] = s
        st.cursor[tl] += 1
    return committed, 0


def _payment(st: State, lanes, inp, round_no, break_si):
    lay = st.lay
    w, d, c, cw = inp["w_id"], inp["d_id"], inp["c_id"], inp["c_w_id"]
    amt = inp["amount"]
    slots = np.stack([lay.w_base + w, lay.d(w, d), lay.c(cw, d, c)], axis=1)
    data = st.cur_data[slots].copy()
    data[:, 0, W_YTD] += amt
    data[:, 1, D_YTD] += amt
    data[:, 2, C_BAL] -= amt
    data[:, 2, C_YTD] += amt
    data[:, 2, C_PAYCNT] += 1
    committed = st.commit(lanes, slots, np.ones(slots.shape, bool), data,
                          break_si)
    ins = committed & (st.hist_cursor[lanes] < lay.opt)
    if ins.any():
        tl = lanes[ins]
        hdata = np.zeros((tl.size, WIDTH), np.int32)
        hdata[:, 0], hdata[:, 1], hdata[:, 2] = amt[ins], c[ins], w[ins]
        st.install(lay.h_base + tl * lay.opt + st.hist_cursor[tl], tl,
                   st.vec[tl], hdata)
        st.hist_cursor[tl] += 1
    return committed, 0


def _delivery(st: State, lanes, inp, round_no, break_si):
    lay = st.lay
    n = lanes.size
    w, d = inp["w_id"], inp["d_id"]
    dsl = lay.d(w, d)
    dist = st.cur_data[dsl].copy()
    found = np.zeros((n,), bool)
    oslot = np.zeros((n,), np.int64)
    for i in range(n):
        o = dist[i, D_NEXT_DELIV]
        if o < dist[i, D_NEXT_O]:
            s = st.index.get(order_key(int(w[i]), int(d[i]), int(o)))
            if s is not None:
                found[i], oslot[i] = True, s
    order = st.cur_data[oslot].copy()
    csl = lay.c(w, d, np.where(found, order[:, O_CID], 0))
    lines = (np.arange(MAX_OL)[None, :] < order[:, O_OLCNT, None]) \
        & found[:, None]
    olsl = lay.ol_base + (oslot - lay.o_base)[:, None] * MAX_OL \
        + np.arange(MAX_OL)[None, :]
    amount = np.where(lines, st.cur_data[np.where(lines, olsl, 0),
                                         OL_AMOUNT], 0).sum(axis=1)
    cust = st.cur_data[csl].copy()
    dist[:, D_NEXT_DELIV] += 1
    order[:, O_CARRIER] = inp["carrier"]
    cust[:, C_BAL] += amount.astype(np.int32)
    cust[:, C_DELCNT] += 1
    slots = np.stack([dsl, oslot, csl], axis=1)
    mask = np.repeat(found[:, None], 3, axis=1)
    committed = st.commit(lanes, slots, mask,
                          np.stack([dist, order, cust], axis=1), break_si)
    return committed, int((committed & found).sum())


def _orderstatus(st: State, inp, last_n):
    n = inp["w_id"].size
    result = np.zeros((n, WIDTH), np.int32)
    found = np.zeros((n,), bool)
    for i in range(n):
        s = st.latest_order(int(inp["w_id"][i]), int(inp["d_id"][i]))
        if s >= 0:
            found[i], result[i] = True, st.cur_data[s]
    return result, found


def _stocklevel(st: State, inp, last_n):
    lay = st.lay
    n = inp["w_id"].size
    counts = np.zeros((n,), np.int32)
    for i in range(n):
        w, d = int(inp["w_id"][i]), int(inp["d_id"][i])
        nxt = int(st.cur_data[lay.d(w, d), D_NEXT_O])
        low = set()
        for o in range(max(nxt - last_n, 0), nxt):
            s = st.index.get(order_key(w, d, o))
            if s is None:
                continue
            for k in range(int(st.cur_data[s, O_OLCNT])):
                item = int(st.cur_data[lay.ol_base + (s - lay.o_base)
                                       * MAX_OL + k, OL_IID])
                if st.cur_data[lay.s(w, item), S_QTY] < inp["threshold"][i]:
                    low.add(item)
        counts[i] = len(low)
    return counts, np.ones((n,), bool)


def replay(sizes: dict, mix, load_key, calls, break_si=False):
    """Load the configuration from ``load_key`` and replay driver calls,
    ``calls`` being ``[(key, n_rounds), …]``. Returns the final state and
    each call's outcome."""
    W, I = int(sizes["n_warehouses"]), int(sizes["n_items"])
    C = int(sizes["customers_per_district"])
    T = mix.lanes_per_warehouse * W
    lay = Layout.make(W, I, C, T, int(sizes["orders_per_thread"]))
    st = State(lay, int(sizes["n_old_versions"]), int(sizes["n_overflow"]),
               load_data(load_key, W, I))
    outs = []
    for key, n in calls:
        rounds = (traffic_mod.to_host(traffic_mod.draw(
            sub, mix, T, W, I, C)) for sub in traffic_mod.round_keys(key, n))
        outs.append(run_call(st, rounds, mix.stock_last_n, break_si))
    return st, outs
