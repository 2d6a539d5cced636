"""The trace reduction on a trace recorded on a TPU v5e (a traced grid of
the ``knobs`` mix at 96 workers, trimmed to 25 ms around the start of the
first device loop, op names cut to 100 characters), checked against
brute-force counts."""

import os

import numpy as np

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")


def _brute_busy(events, lo, hi):
    """Busy ns from a one-bit-per-ns map of the window."""
    busy = np.zeros(hi - lo, bool)
    for _, s, d in events:
        busy[max(s, lo) - lo:max(min(s + d, hi) - lo, 0)] = True
    return int(busy.sum())


def test_busy_and_gaps_agree_with_brute_force():
    tr = trace.read_json(DATA)
    lo, hi = trace.window(tr)
    assert tr.ops, "no device plane in the trace"
    for dev, evs in tr.ops.items():
        busy = trace.busy_ns(evs, lo, hi)
        assert busy == _brute_busy(evs, lo, hi)
        idle = sum(b - a for a, b in trace.gaps(evs, lo, hi))
        assert busy + idle == hi - lo
        assert 0 < busy < hi - lo


def test_executable_time_is_the_module_events():
    tr = trace.read_json(DATA)
    lo, hi = trace.window(tr)
    names = ("_batch_body", "_run_batch_sharded")
    got = trace.executable_ns(tr, names)
    for dev, evs in tr.modules.items():
        want = sum(min(s + d, hi) - max(s, lo) for n, s, d in evs
                   if n.split("(")[0] in ("jit__batch_body",
                                          "jit__run_batch_sharded")
                   and s < hi and s + d > lo)
        assert got[dev] == want
    assert sum(got.values()) > 0


def test_gaps_are_named_by_harness_spans():
    tr = trace.read_json(DATA)
    gaps = trace.idle_gaps(tr)
    assert 0 < len(gaps) <= 10
    assert all(n.startswith("bench.") or n == "host" for n, _ in gaps)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps),
                                          reverse=True)
    ops = trace.top_ops(tr)
    assert 0 < len(ops) <= 10
