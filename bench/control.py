"""The control of ``correct``: the plain reference with the isolation the
configuration states broken, in the program's place, compared as a run is.

    python3 bench/control.py --workload <cell> --seed <n> [--seed <n> ...]

The control grants every write of a sub-round (``reference.replay(...,
break_si=True)``): concurrent writers of a record all commit, the later
lane's install overwriting the earlier one's, which snapshot isolation
forbids. It replays the cell's own calls at the cell's own size (the
warm-up call, then a window of as many rounds as the order capacity
allows, which is where a run's window ends when the cap binds) and prints,
for each seed, every number the comparison makes beside its limit. Each
seed must read above a limit; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import compare  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402


def as_run(st: reference.State, outs) -> tuple:
    """A replay's state, statistics and answers in the shapes a run's
    outputs have (answers over every lane, as the programs return them)."""
    store = {f: getattr(st, f) for f in (
        "cur_hdr", "cur_data", "old_hdr", "old_data", "next_write",
        "ovf_hdr", "ovf_data", "ovf_next", "vec", "cursor", "hist_cursor")}
    store["index"] = dict(st.index)
    stats = [{f: getattr(o, f) for f in compare.STAT_FIELDS
              + ("delivered", "ovf_peak")} for o in outs]
    T = st.lay.T
    answers = []
    for o in outs:
        call = {}
        for t, subs in o.answers.items():
            call[t] = []
            for lanes, res, found in subs:
                r = np.zeros((T,) + res.shape[1:], res.dtype)
                f = np.zeros((T,), bool)
                r[lanes], f[lanes] = res, found
                call[t].append((r, f))
        answers.append(call)
    return store, stats, answers


def readings(sizes: dict, mix, seed: int, n_window: int) -> dict:
    """The control's numbers for one seed: ``{name: (value, limit)}``."""
    import run
    k_load, k_warm, k_win = run.seed_keys(seed)
    calls = [(k_warm, mix.warmup_rounds), (k_win, n_window)]
    good, good_out = reference.replay(sizes, mix, k_load, calls)
    bad, bad_out = reference.replay(sizes, mix, k_load, calls,
                                    break_si=True)
    store, stats, answers = as_run(bad, bad_out)
    return compare.checks(stats, answers, store, good_out, good)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args()
    cell = spec.cell(args.workload)
    sizes = spec.config(cell.config)
    mix = traffic.load_mix(spec.traffic_path(cell.traffic))
    n = int(sizes["orders_per_thread"]) - mix.warmup_rounds
    for seed in args.seed:
        r = readings(sizes, mix, seed, n)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "window_rounds": n,
                          "checks": {k: {"value": v, "limit": lim}
                                     for k, (v, lim) in r.items()},
                          "fails": any(v > lim for v, lim in r.values())}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
