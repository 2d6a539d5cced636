"""setup_s: seconds from the start of the run to the first timed grid:
imports, chip start, graph build, compile or cache load, and the warm-up
of the cell's grid shapes (host clock)."""


def read(run):
    return run.setup_s
