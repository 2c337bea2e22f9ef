"""The comparison that decides ``correct``: the run's outputs against the
plain reference's, every number an exact count with the limit 0.

* ``stats``: the driver's statistics of both calls (per type: attempts,
  commits, retries, snapshot-miss and contention aborts, overflow reads;
  deliveries and the overflow peak), fields that differ;
* ``store``: words of the store that differ: current, old and overflow
  versions with their headers and ring counters, the timestamp vector, the
  order and history extend cursors;
* ``index``: order-index entries (key, slot) held by one side only;
* ``answers``: read-only answers that differ: order-status payloads and
  found flags, stock-level counts.
"""
from __future__ import annotations

import numpy as np

import reference as ref_mod

LIMITS = {"stats": 0, "store": 0, "index": 0, "answers": 0}
STAT_FIELDS = ("attempts", "commits", "retries", "snapshot_misses",
               "contention_aborts", "ovf_reads")


def stats_diff(prog: dict, ref: ref_mod.Outcome) -> int:
    n = 0
    for f in STAT_FIELDS:
        for t in ref_mod.TYPES:
            n += prog[f][t] != getattr(ref, f)[t]
    n += prog["delivered"] != ref.delivered
    n += prog["ovf_peak"] != ref.ovf_peak
    return int(n)


def store_diff(prog: dict, st: ref_mod.State) -> int:
    n = 0
    for f in ("cur_hdr", "cur_data", "old_hdr", "old_data", "next_write",
              "ovf_hdr", "ovf_data", "ovf_next", "vec", "cursor",
              "hist_cursor"):
        a, b = prog[f], getattr(st, f)
        if a.shape != b.shape:
            n += max(a.size, b.size)
        else:
            n += int(np.count_nonzero(a != b))
    return n


def index_diff(prog: dict, st: ref_mod.State) -> int:
    a = set(prog["index"].items())
    b = set(st.index.items())
    return len(a ^ b)


def answers_diff(prog: dict, ref: ref_mod.Outcome) -> int:
    n = 0
    for t, calls in ref.answers.items():
        got = prog[t]
        n += sum(c[0].size for c in calls[len(got):])
        n += sum(r.shape[0] for r, _ in got[len(calls):])
        for (lanes, r_res, r_found), (p_res, p_found) in zip(calls, got):
            p_found = p_found[lanes]
            p_res = p_res[lanes]
            bad = p_found != r_found
            both = p_found & r_found
            if p_res.ndim == 2:
                bad |= both & (p_res != r_res).any(axis=1)
            else:
                bad |= both & (p_res != r_res)
            n += int(bad.sum())
    return n


def checks(prog_stats, prog_answers, prog_store, ref_outcomes, st) -> dict:
    """``{name: (value, limit)}`` over the run's calls (warm-up, window)."""
    return {
        "stats": (sum(stats_diff(p, r) for p, r in
                      zip(prog_stats, ref_outcomes)), LIMITS["stats"]),
        "store": (store_diff(prog_store, st), LIMITS["store"]),
        "index": (index_diff(prog_store, st), LIMITS["index"]),
        "answers": (sum(answers_diff(p, r) for p, r in
                        zip(prog_answers, ref_outcomes)), LIMITS["answers"]),
    }
