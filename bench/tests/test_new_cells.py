"""The cells ``epyc192-dlb`` and ``quad48-small``: their traffic mixes
expand to the grids and chunks their entries in ``BENCHMARK.json``
describe, and the harness finds their files by name."""

import json
import os

from bench.grid import GridSource, expand
from bench.tests.helpers import REPO


def _load(kind, name):
    with open(os.path.join(REPO, "bench", kind, name + ".json")) as f:
        return json.load(f)


def _chunks(config, cases):
    """Each planned chunk's (real lanes, padded lanes)."""
    from bench.harness import Program
    from repro.core.plan import build_plan

    program = Program(config, [])
    plan = build_plan([_Graph()] * len(config["apps"]),
                      program.specs(cases))
    return [(c.n_real, c.padded_size) for c in plan.chunks]


class _Graph:
    """What planning reads of a graph."""
    n_tasks = 1


def test_cells_name_their_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c["file"] for c in bench["configs"]}
    for name, want in (("epyc192-dlb", ("epyc2p192-bots", "dlb192", 1)),
                       ("quad48-small", ("quad48-bots", "small", 1))):
        cell = cells[name]
        assert (cell["config"], cell["traffic"], cell["chips"]) == want
        assert configs[cell["config"]] == \
            f"bench/configs/{cell['config']}.json"
        assert _load("traffic", cell["traffic"])["check_rows"] == 12


def test_dlb192_is_36_cases_in_3_chunks_of_12():
    traffic, config = _load("traffic", "dlb192"), \
        _load("configs", "epyc2p192-bots")
    cases = expand(traffic, config, seeds=traffic["case_seeds"][0])
    assert len(cases) == 36
    assert {c["app"] for c in cases} == {"fib", "sort", "uts"}
    assert {(c["queue"], c["barrier"]) for c in cases} == {("xqueue", "tree")}
    assert {c["balance"] for c in cases} == {"static_rr", "na_rp", "na_ws"}
    assert {c["p_local"] for c in cases} == {1.0, 0.25}
    assert {(c["n_victim"], c["n_steal"], c["t_interval"])
            for c in cases} == {(4, 8, 100)}
    assert {c["n_workers"] for c in cases} == {192}
    assert _chunks(config, cases) == [(12, 16)] * 3
    assert len(traffic["case_seeds"]) == 4
    assert all(len(s) == 2 for s in traffic["case_seeds"])


def test_small_is_12_cases_in_12_one_lane_chunks():
    traffic, config = _load("traffic", "small"), _load("configs", "quad48-bots")
    cases = expand(traffic, config, seeds=traffic["case_seeds"][0])
    assert len(cases) == 12
    assert {c["app"] for c in cases} == {"sort"}
    assert len({(c["queue"], c["barrier"], c["balance"])
                for c in cases}) == 12
    assert {(c["n_victim"], c["n_steal"], c["t_interval"], c["p_local"])
            for c in cases} == {(4, 8, 100, 1.0)}
    assert {c["n_workers"] for c in cases} == {48}
    assert _chunks(config, cases) == [(1, 1)] * 12
    assert len(traffic["case_seeds"]) == 8
    assert all(len(s) == 1 for s in traffic["case_seeds"])


def _pool_is_drawn_as_the_others(name, config, k):
    """Case seeds from ``numpy.random.default_rng(k).integers(2**31 - 1,
    size=(grids, seeds))``, as the ``lattice`` and ``knobs`` mixes draw
    theirs, and every grid of the pool is sent once per round."""
    import numpy as np

    t = _load("traffic", name)
    shape = (len(t["case_seeds"]), len(t["case_seeds"][0]))
    assert t["case_seeds"] == np.random.default_rng(k).integers(
        2 ** 31 - 1, size=shape).tolist()
    src = GridSource(t, _load("configs", config), 2 ** 31 + 7)
    sent = [tuple(sorted({c["seed"] for c in src.next()}))
            for _ in range(shape[0])]
    assert sorted(sent) == sorted(tuple(sorted(s)) for s in t["case_seeds"])


def test_small_pool_is_drawn_as_the_others():
    _pool_is_drawn_as_the_others("small", "quad48-bots", 2028)


def test_dlb192_pool_is_drawn_as_the_others():
    _pool_is_drawn_as_the_others("dlb192", "epyc2p192-bots", 2027)
