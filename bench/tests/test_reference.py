"""The sequential reference reproduces the simulator's pinned rows: the
golden cases of the five legacy runtime modes (flat machine), and the
program's rows at the quad-socket topology for every runtime lattice point
and every DLB knob of the tuner's grid."""

import itertools
import json
import os

import numpy as np
import pytest

from bench import check
from bench import graphs as graphs_mod
from bench.reference.sim import simulate
from bench.tests.helpers import REPO, tiny_config

MODES = {"gomp": ("locked_global", "centralized_count", "static_rr"),
         "xgomp": ("xqueue", "centralized_count", "static_rr"),
         "xgomptb": ("xqueue", "tree", "static_rr"),
         "na_rp": ("xqueue", "tree", "na_rp"),
         "na_ws": ("xqueue", "tree", "na_ws")}

with open(os.path.join(REPO, "bench", "tests", "data",
                       "golden_modes.json")) as f:
    GOLDEN = json.load(f)


@pytest.mark.parametrize("i", range(len(GOLDEN["cases"])))
def test_golden_case(i):
    want = GOLDEN["cases"][i]
    builder, kw = GOLDEN["graphs"][want["graph"]]
    graph = graphs_mod.BUILDERS[builder](**kw, seed=0)
    queue, barrier, balance = MODES[want["mode"]]
    case = dict(queue=queue, barrier=barrier, balance=balance,
                n_workers=GOLDEN["cfg"]["n_workers"], seed=0,
                p_local_node=0.75, **GOLDEN["knobs"])
    sim = dict(queue_cap=16, stack_cap=512,
               max_steps=GOLDEN["cfg"]["max_steps"],
               n_zones=GOLDEN["cfg"]["n_zones"])
    costs = tiny_config("quad48-bots")["costs"]
    row = simulate(graph, case, sim, None, costs)
    assert row["time_ns"] == want["time_ns"]
    assert row["steps"] == want["steps"]
    assert {k: row["counters"][k] for k in want["counters"]} \
        == want["counters"]


def test_program_rows_at_the_quad_socket_machine():
    from bench.harness import Program

    cfg = tiny_config("quad48-bots", n_workers=48)
    for app, args in zip(cfg["apps"], ({"n": 10}, {"levels": 5},
                                       {"n_target": 500})):
        app["args"] = args
    graphs = [graphs_mod.build(a, cfg["graph_seed"]) for a in cfg["apps"]]
    rng = np.random.default_rng(7)
    base = dict(n_workers=48, n_victim=4, n_steal=8, t_interval=100,
                p_local=1.0, p_local_node=0.75)
    cases = [dict(base, app=a["name"], graph=gi, queue=q, barrier=b,
                  balance=bl, seed=int(rng.integers(2 ** 31)))
             for gi, a in enumerate(cfg["apps"])
             for q, b, bl in itertools.product(
                 ("locked_global", "xqueue"), ("centralized_count", "tree"),
                 ("static_rr", "na_rp", "na_ws"))]
    knobs = list(itertools.product((1, 4, 12), (1, 8, 32), (10, 100),
                                   (1.0, 0.25)))
    cases += [dict(base, app="uts", graph=2, queue="xqueue", barrier="tree",
                   balance=bl, n_victim=nv, n_steal=ns, t_interval=ti,
                   p_local=pl, seed=int(rng.integers(2 ** 31)))
              for k, (nv, ns, ti, pl) in enumerate(knobs)
              for bl in (("na_rp", "na_ws")[k % 2],)]
    program = Program(cfg, graphs)
    res = program.run(program.specs(cases))
    assert res.completed.all()
    bad = [(c, check.mismatches(check.program_row(res, i), simulate(
        graphs[c["graph"]], c, cfg["sim"], cfg["machine"], cfg["costs"])))
        for i, c in enumerate(cases)]
    assert [b for b in bad if b[1]] == []
