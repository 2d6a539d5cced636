"""Trace reduction: a profiler trace to device busy time, idle gaps and the
device time of named executables.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`load` keeps what the metrics read, as a :class:`Trace`:

* per device plane (``/device:TPU:<n>``), the intervals of its ``XLA Ops``
  line (every operation that ran) and of its ``XLA Modules`` line (one
  event per executable run, named ``jit_<function>(<id>)``);
* the harness's own host spans (``TraceAnnotation`` names starting with
  ``bench.``), on the same clock.

Busy time is the union of a device's op intervals inside the window; idle
is the rest of the window.  The profiler keeps a bounded number of device
events and marks the point where it dropped the rest (``Trace Buffers
Dropped``); a trace cut short that way is refused, since its end would read
as idle.  A gap is attributed to the innermost harness
span that covers its midpoint.  A ``Trace`` also reads from JSON, so a
trimmed copy of a chip trace can check this module without a chip.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
DROPPED = "Trace Buffers Dropped"
NAME_CHARS = 100


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]       # device plane -> op events
    modules: Dict[str, List[Event]]   # device plane -> executable events
    host: List[Event]                 # harness spans

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        def evs(xs):
            return [(str(n), int(s), int(u)) for n, s, u in xs]
        return cls(ops={k: evs(v) for k, v in d["ops"].items()},
                   modules={k: evs(v) for k, v in d["modules"].items()},
                   host=evs(d["host"]))


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dst = ops if line.name == OPS_LINE else modules
                    dst.setdefault(plane.name, []).extend(
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events)
                elif any(e.name == DROPPED for e in line.events):
                    raise ValueError(f"{plane.name}: the profiler dropped "
                                     "device events; trace less")
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return Trace(ops=ops, modules=modules, host=host)


def window(trace: Trace) -> Tuple[int, int]:
    """The traced window: the harness's ``bench.window`` span."""
    spans = [(s, s + d) for n, s, d in trace.host if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    return spans[0]


def _merged(events: List[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Union of the events' intervals, clipped to [lo, hi), in order."""
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                if s < hi and s + d > lo)
    out: List[List[int]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events: List[Event], lo: int, hi: int) -> int:
    return sum(b - a for a, b in _merged(events, lo, hi))


def gaps(events: List[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The idle intervals of [lo, hi) between merged busy intervals."""
    out, t = [], lo
    for a, b in _merged(events, lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def span_at(host: List[Event], t: int) -> str:
    """Name of the innermost harness span covering time ``t``."""
    best, best_start = "host", None
    for n, s, d in host:
        if n != WINDOW_SPAN and s <= t < s + d and (
                best_start is None or s >= best_start):
            best, best_start = n, s
    return best


def device_busy(trace: Trace) -> Dict[str, float]:
    """Busy seconds of each device in the window."""
    lo, hi = window(trace)
    return {dev: busy_ns(evs, lo, hi) / 1e9 for dev, evs in trace.ops.items()}


def idle_gaps(trace: Trace, k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` longest idle gaps over all devices, each named by the
    harness span the host was in."""
    lo, hi = window(trace)
    out = []
    for dev, evs in trace.ops.items():
        for a, b in gaps(evs, lo, hi):
            out.append((span_at(trace.host, (a + b) // 2), (b - a) / 1e9))
    out.sort(key=lambda x: -x[1])
    return out[:k]


def top_ops(trace: Trace, k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` device operations that took the most time in the window,
    summed over devices and over every run of the op.  An op is named by
    the first ``NAME_CHARS`` characters of its HLO text; a ``while`` op's
    time includes the ops of its body."""
    lo, hi = window(trace)
    tot: Dict[str, int] = defaultdict(int)
    for evs in trace.ops.values():
        for n, s, d in evs:
            if s < hi and s + d > lo:
                tot[n[:NAME_CHARS]] += min(s + d, hi) - max(s, lo)
    return sorted(((n, v / 1e9) for n, v in tot.items()),
                  key=lambda x: -x[1])[:k]


def executable_name(event_name: str) -> str:
    """``jit__batch_body(123)`` -> ``_batch_body``."""
    base = event_name.split("(")[0]
    return base[len("jit_"):] if base.startswith("jit_") else base


def executable_ns(trace: Trace, names) -> Dict[str, int]:
    """Device time, per device, of the executables whose function name is
    in ``names``, inside the window."""
    lo, hi = window(trace)
    names = set(names)
    return {dev: sum(min(s + d, hi) - max(s, lo) for n, s, d in evs
                     if executable_name(n) in names and s < hi and s + d > lo)
            for dev, evs in trace.modules.items()}


def read_json(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
