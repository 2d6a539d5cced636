"""Lane and loop-iteration accounting of one grid, from the program's plan
and its result rows.

The batched executors run each chunk as one ``while`` loop over a padded
batch of lanes; a lane's ``steps`` is the number of iterations in which it
was alive, and padding lanes take no step.  Each device runs the loop over
its own contiguous slice of the chunk's lanes until the slice's slowest
lane stops, so a slice's iteration count is the largest step count among
its lanes.  On one device the slice is the whole padded chunk; the serial
executor (host CPU only) runs one loop per case.
"""

from __future__ import annotations

import dataclasses
from typing import List


@dataclasses.dataclass(frozen=True)
class Slice:
    """One device's share of one chunk."""
    lanes: int           # padded lanes on this device
    steps: tuple         # steps of the real lanes on it

    @property
    def iterations(self) -> int:
        return max(self.steps, default=0)


def slices(graphs, specs, steps) -> List[Slice]:
    """Every (chunk, device) slice of a grid run with ``strategy="auto"``.

    ``graphs``/``specs`` are what the grid passed to ``run_cases``,
    ``steps`` the result's per-case step counts in ``specs`` order."""
    from repro.core.executors import select_executor
    from repro.core.plan import build_plan

    import jax

    out = []
    for chunk in build_plan(graphs, specs).chunks:
        ex = select_executor("auto", chunk)
        lane_steps = [int(steps[i]) for i in chunk.indices]
        if ex.name == "serial":     # one loop per case (host CPU only)
            out.extend(Slice(lanes=1, steps=(s,)) for s in lane_steps)
            continue
        padded = ex.padded_size(chunk)
        n_dev = jax.device_count() if ex.name == "sharded" else 1
        lane_steps += [0] * (padded - len(lane_steps))
        per = padded // n_dev
        for d in range(n_dev):
            mine = lane_steps[d * per:(d + 1) * per]
            out.append(Slice(lanes=per, steps=tuple(s for s in mine if s)))
    return out


def useful_and_capacity(sl: List[Slice]) -> tuple:
    """(Σ real lane-steps, Σ padded lanes × slice iterations)."""
    return (sum(sum(s.steps) for s in sl),
            sum(s.lanes * s.iterations for s in sl))
