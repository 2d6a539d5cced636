"""rows_per_s: result rows of the grids completed in the window over the
window's whole time, first grid sent to last grid's rows back (host
clock)."""


def read(run):
    return run.rows / run.window_s
