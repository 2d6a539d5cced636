"""The check that decides ``correct``: rows of the timed grids against the
plain reference, field by field, exactly.

Once the window has closed, a sample of its rows drawn from the run's seed
is simulated again by :mod:`bench.reference.sim`, a sequential loop on
the host: the row with the most steps in the window, then rows from every
runtime lattice point in turn.  A row matches when ``time_ns``, ``steps``,
``completed``, every counter and the SLO fields are all equal.  The
simulator is deterministic integer arithmetic apart from two float32
expressions, so the limit is 0 mismatched fields.
"""

from __future__ import annotations

import sys
import time

import numpy as np

SCALARS = ("time_ns", "steps", "completed", "p50_ns", "p90_ns", "p99_ns",
           "throughput_tasks_per_s")
SPEC_KEYS = ("queue", "barrier", "balance")


def program_row(res, i: int) -> dict:
    """Row ``i`` of a ``SweepResult`` in the reference's layout."""
    return dict(time_ns=int(res.time_ns[i]), steps=int(res.steps[i]),
                completed=bool(res.completed[i]),
                counters={k: int(v[i]) for k, v in res.counters.items()},
                p50_ns=float(res.p50_ns[i]), p90_ns=float(res.p90_ns[i]),
                p99_ns=float(res.p99_ns[i]),
                throughput_tasks_per_s=float(res.throughput[i]))


def sample(grids, n: int, seed: int) -> list:
    """``n`` (grid, row) pairs: the row with the most steps, then one row
    from each runtime lattice point in turn, drawn from ``seed``."""
    rng = np.random.default_rng([seed, 1])
    pairs = [(g, i) for g, run in enumerate(grids)
             for i in range(len(run.cases))]
    longest = max(pairs, key=lambda p: grids[p[0]].result.steps[p[1]])
    groups: dict = {}
    for g, i in pairs:
        key = tuple(grids[g].cases[i][k] for k in SPEC_KEYS)
        groups.setdefault(key, []).append((g, i))
    picked = [longest]
    keys = list(groups)
    k = 0
    while len(picked) < min(n, len(pairs)):
        pool = [p for p in groups[keys[k % len(keys)]] if p not in picked]
        if pool:
            picked.append(pool[rng.integers(len(pool))])
        k += 1
    return picked


def mismatches(prog: dict, ref: dict) -> list:
    bad = [f for f in SCALARS if prog[f] != ref[f]]
    bad += [f"counters.{c}" for c, v in ref["counters"].items()
            if prog["counters"].get(c) != v]
    return bad


def run(grids, graphs, config: dict, n: int, seed: int) -> dict:
    """Compare ``n`` sampled rows with the reference; returns the numbers
    compared, each with its limit."""
    from bench.reference.sim import simulate

    t0 = time.perf_counter()
    picked = sample(grids, n, seed)
    bad_rows = bad_fields = 0
    for g, i in picked:
        case = grids[g].cases[i]
        ref = simulate(graphs[case["graph"]], case, config["sim"],
                       config.get("machine"), config["costs"])
        bad = mismatches(program_row(grids[g].result, i), ref)
        if bad:
            bad_rows += 1
            bad_fields += len(bad)
            print(f"check: grid {g} row {i} {case}: differs in "
                  f"{', '.join(bad)}", file=sys.stderr)
    print(f"check: {len(picked)} rows against the reference in "
          f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
    return dict(rows_compared=dict(
                    value=len(picked),
                    limit=min(n, sum(len(r.cases) for r in grids))),
                mismatched_rows=dict(value=bad_rows, limit=0),
                mismatched_fields=dict(value=bad_fields, limit=0))


def passed(numbers: dict) -> bool:
    return (numbers["rows_compared"]["value"]
            >= numbers["rows_compared"]["limit"]
            and numbers["mismatched_rows"]["value"] == 0
            and numbers["mismatched_fields"]["value"] == 0)
