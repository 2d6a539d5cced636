"""lane_step_share: the share of the device loop's lane-steps that advance
a real case.  Counted from the program's plan of each traced grid and its
result rows: Σ real lanes' steps over Σ (padded lanes × loop iterations),
per device slice of each chunk (see bench.lanes)."""

from bench import lanes


def read(run):
    useful = capacity = 0
    for g in run.grids:
        u, c = lanes.useful_and_capacity(
            lanes.slices(run.graphs, g.specs, g.result.steps))
        useful += u
        capacity += c
    return useful / capacity if capacity else None
