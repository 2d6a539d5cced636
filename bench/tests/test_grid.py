"""The generic grid builder expands each traffic mix to its grid."""

import json
import os

from bench.grid import GridSource, expand
from bench.tests.helpers import REPO


def _load(kind, name):
    with open(os.path.join(REPO, "bench", kind, name + ".json")) as f:
        return json.load(f)


def test_lattice_is_72_cases():
    cases = expand(_load("traffic", "lattice"),
                   _load("configs", "quad48-bots"), seeds=[5, 6])
    assert len(cases) == 72
    specs = {(c["queue"], c["barrier"], c["balance"]) for c in cases}
    assert len(specs) == 12
    assert {c["app"] for c in cases} == {"fib", "sort", "uts"}
    assert {c["n_workers"] for c in cases} == {48}


def test_knobs_is_144_cases():
    cases = expand(_load("traffic", "knobs"),
                   _load("configs", "quad48-bots"), seeds=[5, 6])
    assert len(cases) == 144
    knobs = {(c["n_victim"], c["n_steal"], c["t_interval"], c["p_local"])
             for c in cases}
    assert len(knobs) == 36
    assert {c["balance"] for c in cases} == {"na_rp", "na_ws"}
    assert {c["app"] for c in cases} == {"uts"}
    assert {c["n_workers"] for c in cases} == {48}


def test_seed_orders_a_fixed_pool():
    t, c = _load("traffic", "lattice"), _load("configs", "quad48-bots")
    big = 2 ** 31 + 12345
    k = len(t["case_seeds"])
    a, b = GridSource(t, c, big), GridSource(t, c, big)
    mine = [a.next() for _ in range(k)]
    assert mine == [b.next() for _ in range(k)]
    assert a.next() == mine[0]                  # then round again
    other = GridSource(t, c, big + 1)
    theirs = [other.next() for _ in range(k)]
    assert theirs != mine                       # another order ...
    assert sorted(map(json.dumps, theirs)) == sorted(map(json.dumps, mine))
    assert {tuple(sorted({x["seed"] for x in g})) for g in mine} == {
        tuple(sorted(s)) for s in t["case_seeds"]}  # ... of the same pool
