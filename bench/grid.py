"""The generic grid builder: a traffic mix's axes expanded into cases.

A traffic mix is a JSON file in ``bench/traffic/`` named after the mix.  It
lists the configuration's apps to run, the runtime lattice axes
(``queue``, ``barrier``, ``balance``), the DLB knob axes (``n_victim``,
``n_steal``, ``t_interval``, ``p_local``, ``p_local_node``) and the case
seeds: ``case_seeds``, one list per grid.  A grid is the cartesian product
of the axes and its case seeds (apps outermost, seeds innermost).  The
loop is closed with one client: the next grid goes out when the last one's
rows are back.

Every run sends the same pool of grids, in an order drawn from its
``--seed`` and then round again, so that runs differ in order and in the
rows sampled for the check, not in the work: a case seed fixes a case's
step count, and seeds drawn afresh would make the seed, not the program,
the largest source of spread.  Size the pool to the grids one window holds.
"""

from __future__ import annotations

import itertools

import numpy as np

SPEC_AXES = ("queue", "barrier", "balance")
KNOB_AXES = ("n_victim", "n_steal", "t_interval", "p_local", "p_local_node")
KNOB_DEFAULTS = dict(n_victim=4, n_steal=8, t_interval=100, p_local=1.0,
                     p_local_node=0.75)


def expand(traffic: dict, config: dict, seeds) -> list:
    """Every case of one grid as a plain dict (see ``reference.simulate``)."""
    apps = [a["name"] for a in config["apps"]]
    chosen = traffic.get("apps", apps)
    unknown = set(chosen) - set(apps)
    if unknown:
        raise ValueError(f"traffic names apps {sorted(unknown)} that the "
                         f"configuration lacks ({apps})")
    axes = [traffic["axes"][a] for a in SPEC_AXES]
    knobs = [traffic.get("knobs", {}).get(k, [KNOB_DEFAULTS[k]])
             for k in KNOB_AXES]
    cases = []
    for app in chosen:
        for spec in itertools.product(*axes):
            for knob in itertools.product(*knobs):
                for seed in seeds:
                    cases.append(dict(
                        app=app, graph=apps.index(app),
                        n_workers=config["n_workers"], seed=int(seed),
                        **dict(zip(SPEC_AXES, spec)),
                        **dict(zip(KNOB_AXES, knob))))
    return cases


class GridSource:
    """The grid sequence of one run: the same ``--seed`` issues the same
    grids in the same order."""

    def __init__(self, traffic: dict, config: dict, seed: int):
        self.traffic = traffic
        self.config = config
        self.order = np.random.default_rng(seed).permutation(
            len(traffic["case_seeds"]))
        self.sent = 0

    def next(self) -> list:
        k = self.order[self.sent % len(self.order)]
        self.sent += 1
        return expand(self.traffic, self.config,
                      self.traffic["case_seeds"][k])
