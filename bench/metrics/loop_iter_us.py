"""loop_iter_us: device microseconds per iteration of the device loop.

The device time of the executables that run the batched ``while`` loop
(``executors._run_batch`` and ``_run_batch_sharded``),
summed over devices, over the loop iterations they ran: per chunk and
device slice, the largest step count among its lanes (see bench.lanes)."""

from bench import lanes, trace

#: the program's jitted functions that hold the loop, as the trace names
#: them: ``executors._run_batch`` jits ``_batch_body`` (vmap), and
#: ``_run_batch_sharded`` is its own function (sharded)
EXECUTABLES = ("_batch_body", "_run_batch_sharded")


def read(run):
    if run.trace is None:
        return None
    ns = sum(trace.executable_ns(run.trace, EXECUTABLES).values())
    iters = sum(s.iterations for g in run.grids
                for s in lanes.slices(run.graphs, g.specs, g.result.steps))
    if ns == 0 or iters == 0:
        return None
    return ns / 1e3 / iters
