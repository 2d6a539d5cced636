"""The 192-worker, eight-NUMA-node machine (``topology.PRESETS
["epyc_2p_nps4"]``: a 2-socket EPYC 9654 in NPS4) through the normal
path, ``run_grid`` with ``strategy="auto"`` and the default backend,
against the plain sequential reference of the benchmark
(``bench/reference/sim.py``, which shares no code with the program), field
for field: at its full 192 workers, and at 20, where the last of the eight
domains takes the remainder workers (2 a domain, 6 in the last).  The
graphs are the benchmark's BOTS apps at tiny sizes, with its simulator
sizes and cost model."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import check  # noqa: E402
from bench import graphs as graphs_mod  # noqa: E402
from bench.reference.sim import simulate  # noqa: E402
from repro.core.costs import CostModel  # noqa: E402
from repro.core.state import SimConfig  # noqa: E402
from repro.core.sweep import run_grid  # noqa: E402
from repro.core.taskgraph import TaskGraph  # noqa: E402
from repro.core.topology import PRESETS  # noqa: E402

#: the benchmark's configuration of this machine: its BOTS apps, simulator
#: sizes and cost model
with open(os.path.join(REPO, "bench", "configs", "epyc2p192-bots.json")) as f:
    CONFIG = json.load(f)
MACHINE = PRESETS["epyc_2p_nps4"].asdict()
#: the NPS4 SLIT table the preset scales by c_zone / 10
SLIT = [[10 if i == j else 12 if i // 4 == j // 4 else 32 for j in range(8)]
        for i in range(8)]

BALANCERS = ("static_rr", "na_rp", "na_ws")
P_LOCAL = (1.0, 0.25)
WIDTHS = (192, 20)
SEED = 1_234_567_891
TINY_ARGS = {"fib": {"n": 8}, "sort": {"levels": 4}, "uts": {"n_target": 300}}


def test_preset_is_the_nps4_table_scaled():
    t = PRESETS["epyc_2p_nps4"]
    c_zone = CONFIG["costs"]["c_zone"]
    assert (t.n_sockets, t.cores_per_socket, t.natural_workers) == (8, 24, 192)
    assert [list(r) for r in t.dist] == [[d * c_zone // 10 for d in r]
                                         for r in SLIT]
    assert not t.is_flat and not t.is_cluster


def test_preset_is_the_benchmark_machine():
    """The benchmark's ``epyc2p192-bots`` runs this preset at its full
    width: the configuration's machine is the preset's, field for field."""
    m = CONFIG["machine"]
    t = PRESETS["epyc_2p_nps4"]
    assert (m["name"], m["n_sockets"], m["cores_per_socket"]) == (
        t.name, t.n_sockets, t.cores_per_socket)
    assert [list(r) for r in t.dist] == m["dist"]
    assert CONFIG["n_workers"] == t.natural_workers == 192


@pytest.fixture(scope="module")
def grid():
    """One ``run_grid`` over both widths; returns (result, graphs)."""
    apps = [dict(a, args=TINY_ARGS[a["builder"]]) for a in CONFIG["apps"]]
    graphs = [TaskGraph(**graphs_mod.build(a, CONFIG["graph_seed"]).fields())
              for a in apps]
    sim = CONFIG["sim"]
    cfg = SimConfig(n_workers=max(WIDTHS), queue_cap=sim["queue_cap"],
                    stack_cap=sim["stack_cap"], max_steps=sim["max_steps"],
                    costs=CostModel(**CONFIG["costs"]))
    res = run_grid(graphs, queues=("xqueue",), barriers=("tree",),
                   balancers=BALANCERS, topologies=("epyc_2p_nps4",),
                   n_workers=WIDTHS, seeds=(SEED,), p_local=P_LOCAL,
                   cfg=cfg)
    return res, graphs


@pytest.mark.parametrize("n_workers", WIDTHS)
@pytest.mark.parametrize("p_local", P_LOCAL)
@pytest.mark.parametrize("balance", BALANCERS)
def test_rows_match_the_reference(grid, balance, p_local, n_workers):
    res, graphs = grid
    mine = [i for i, s in enumerate(res.specs)
            if (s.spec.balance, s.p_local, s.n_workers)
            == (balance, p_local, n_workers)]
    assert len(mine) == len(graphs)
    for i in mine:
        s = res.specs[i]
        assert s.topology is PRESETS["epyc_2p_nps4"]
        case = dict(queue=s.spec.queue, barrier=s.spec.barrier,
                    balance=s.spec.balance, n_workers=s.n_workers,
                    seed=s.seed, n_victim=s.n_victim, n_steal=s.n_steal,
                    t_interval=s.t_interval, p_local=s.p_local,
                    p_local_node=s.p_local_node)
        ref = simulate(graphs[s.graph], case, CONFIG["sim"], MACHINE,
                       CONFIG["costs"])
        assert ref["completed"]
        assert check.mismatches(check.program_row(res, i), ref) == [], \
            graphs[s.graph].name
