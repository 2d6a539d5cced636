"""The program's own spans and scopes in a profiler trace.

The program marks its host work with spans named ``repro.<stage>`` (a
``jax.profiler.TraceAnnotation`` per call and per chunk, whose arguments
``call``, ``chunk``, ``lanes`` and ``padded`` come back as event stats),
and the phases of its step with ``jax.named_scope`` (``adopt`` ... ``exec``,
``gate``, ``occupancy``), which the compiler keeps as each instruction's
``op_name`` metadata.  :class:`ProgramTrace` is a :class:`bench.trace.Trace`
that also holds both: the ``repro.`` spans with their arguments, and for
each device op inside a loop executable the scope it ran under, filled in
by the first step reader.  Its ``host`` list holds the ``repro.`` spans
beside the harness's ``bench.`` ones, so ``trace.idle_gaps`` names a gap
by the innermost span of either kind.

On the TPU the ``XLA Ops`` events carry no metadata, and a trace holds no
HLO of executables loaded from the persistent compile cache.  So the
scopes come from the HLO text of the loop executables still alive in the
process that ran them: the harness's, which reads the trace right after.

The harness reads a trace with ``bench.trace.load`` and deletes it after;
:func:`install` has that call read a :class:`ProgramTrace`: the same
``bench.trace.load``, then the host planes' ``repro.`` spans.  The readers
of the metrics below call it when they are loaded, which is before the
traced run.  A trace of a program without spans or scopes reads as before,
and those readers return ``None`` on it, as the step readers do when more
than :data:`MAX_UNSCOPED` of the loops' leaf-op time has no scope.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import os
import re
from typing import Dict, List, Optional, Tuple

from bench import lanes, trace

#: the benchmark's own ``load``, which :func:`install` replaces
_TRACE_LOAD = trace.load

PREFIX = "repro."
#: the step's phases, then the loop gate and the cluster occupancy charge
PHASES = ("adopt", "spawn", "dequeue", "thief", "victim", "exec")
SCOPES = PHASES + ("gate", "occupancy")
#: the program's jitted functions that hold the device loop, as
#: ``loop_iter_us`` reads them
LOOP_EXECUTABLES = ("_batch_body", "_run_batch_sharded")
#: host spans over which device idle time is not the program's host work
NOT_HOST_WORK = ("repro.wait", "repro.run_cases")
#: the step readers return ``None`` when more of the loops' leaf-op time
#: than this falls under no scope: the scopes were not read (a table of
#: another executable, or a compiler that drops the metadata), and phases
#: read from the rest would look plausible and be wrong
MAX_UNSCOPED = 0.10

# a scope is one component of an op_name path, maybe inside transform
# wrappers: "jit(_batch_body)/while/body/vmap(spawn)/while/body/ge"
_SCOPE = re.compile(r"(?:^|/)(?:[\w.]+\()*(" + "|".join(SCOPES)
                    + r")\)*(?=/|$)")
# HLO text: a computation's head ("%fused_computation.88 (p: s32[8]) ->
# s32[8] {", "ENTRY %main.1 ..."), and an instruction ("  ROOT %fusion.833 =
# s32[18432]{0} fusion(%p.1, %p.2), kind=kLoop, calls=%fused_computation.88,
# metadata={op_name="jit(_batch_body)/while/body/vmap(spawn)/add" ...}")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?(%\S+) .*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?((%\S+) = \S+) ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=(%[\w.\-]+)")
_OPERAND = re.compile(r"(?<![=\w])(%[\w.\-]+)")

Span = Tuple[str, int, int, dict]     # (name, start_ns, duration_ns, args)


def scope_of(op_name: str) -> str:
    """The innermost of :data:`SCOPES` in an ``op_name`` path, or ``""``."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else ""


def _op_key(name: str) -> str:
    """An op event's name is its HLO instruction's text; the key is the
    instruction's name and shape: ``"%fusion.833 = s32[18432]{0}"``."""
    i = name.find(" = ")
    j = name.find(" ", i + 3) if i >= 0 else -1
    return name[:j] if j >= 0 else name


def loop_tables() -> Dict[str, List[Dict[str, str]]]:
    """Per loop function (``jit__batch_body``), one table per live compiled
    executable: op key -> scope, from the ``op_name`` metadata in the
    executable's HLO text.  The profiler's device events carry no
    metadata, and its trace keeps no HLO of executables loaded from the
    persistent cache, so the process that ran them is asked."""
    from jax.extend import backend

    out: Dict[str, List[Dict[str, str]]] = {}
    for ex in backend.get_backend().live_executables():
        for m in ex.hlo_modules():
            if trace.executable_name(m.name) in LOOP_EXECUTABLES:
                out.setdefault(m.name, []).append(hlo_table(m.to_string()))
    return out


def hlo_table(text: str) -> Dict[str, str]:
    """Op key -> scope of a module's HLO text.

    An instruction takes the scope of its own ``op_name``.  The compiler
    leaves some without one, such as the scatters it rewrites and the
    reshapes and copies of its layouts; such an instruction takes the scope
    most of its fused computation's instructions carry, else that of the
    nearest user with one, else that of the nearest operand.  A ``while``
    keeps its own: the loop's time between its body's ops is no phase's."""
    comps: Dict[str, List[str]] = {}     # computation -> its instructions
    key_of: Dict[str, str] = {}
    own: Dict[str, str] = {}
    calls: Dict[str, List[str]] = {}
    operands: Dict[str, List[str]] = {}
    loops = set()
    current: List[str] = []
    for line in text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            current = comps.setdefault(head.group(1), [])
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        key, name = m.group(1), m.group(2)
        current.append(name)
        key_of[name] = key
        meta = _OP_NAME.search(line)
        own[name] = scope_of(meta.group(1)) if meta else ""
        calls[name] = _CALLS.findall(line)
        if " while(" in line:
            loops.add(name)
        operands[name] = _OPERAND.findall(line[m.end():])
    users: Dict[str, List[str]] = {}
    for name, ops in operands.items():
        for op in ops:
            users.setdefault(op, []).append(name)

    def nearest(name: str, step: Dict[str, List[str]]) -> str:
        seen, frontier = {name}, [name]
        while frontier:
            frontier = [n for f in frontier for n in step.get(f, [])
                        if n in own and n not in seen]
            seen.update(frontier)
            found = [own[n] for n in frontier if own[n]]
            if found:
                return found[0]
        return ""

    table: Dict[str, str] = {}
    for name, key in key_of.items():
        inner = collections.Counter(
            own[n] for c in calls[name] for n in comps.get(c, []) if own[n])
        sc = own[name] if name in loops else (
            own[name] or (inner.most_common(1)[0][0] if inner else "")
            or nearest(name, users) or nearest(name, operands))
        if sc:
            table[key] = sc
    return table


def _op_scopes(evs: List[trace.Event], modules: List[trace.Event],
               tables: Dict[str, List[Dict[str, str]]]) -> List[str]:
    """The scope of each op event inside a loop executable's event.  Of the
    live executables of the event's function, the one that ran it is the
    one whose table knows most of its ops' keys."""
    out = [""] * len(evs)
    loops = sorted((s, s + d, n.split("(")[0]) for n, s, d in modules
                   if trace.executable_name(n) in LOOP_EXECUTABLES)
    starts = [a for a, _, _ in loops]
    members: Dict[int, List[int]] = {}
    for i, (_, s, _) in enumerate(evs):
        k = bisect.bisect_right(starts, s) - 1
        if k >= 0 and s < loops[k][1]:
            members.setdefault(k, []).append(i)
    for k, idx in members.items():
        cands = tables.get(loops[k][2])
        if not cands:
            continue
        keys = [_op_key(evs[i][0]) for i in idx]
        distinct = set(keys)
        best = max(cands, key=lambda t: len(distinct & t.keys()))
        for i, key in zip(idx, keys):
            out[i] = best.get(key, "")
    return out


@dataclasses.dataclass
class LoopTime:
    """Device ns inside the loop executables, summed over devices.  An op's
    self time is its duration less the ops it contains."""
    executables: int                  # the executables' own events
    leaf: Dict[str, int]              # scope ("" none) -> self ns of leaves
    outer: Dict[str, int]             # scope -> self ns of ops holding ops

    def scoped(self, scope: str) -> int:
        return self.leaf.get(scope, 0) + self.outer.get(scope, 0)

    def unscoped_share(self) -> float:
        """Share of the leaves' self time that falls under no scope."""
        total = sum(self.leaf.values())
        return self.leaf.get("", 0) / total if total else 1.0


@dataclasses.dataclass
class ProgramTrace(trace.Trace):
    program: List[Span] = dataclasses.field(default_factory=list)
    scopes: Dict[str, List[str]] = dataclasses.field(default_factory=dict)
    _loop: Optional[LoopTime] = dataclasses.field(
        default=None, repr=False, compare=False)

    @classmethod
    def from_json(cls, d: dict) -> "ProgramTrace":
        base = trace.Trace.from_json(d)
        return cls(ops=base.ops, modules=base.modules, host=base.host,
                   program=[(str(n), int(s), int(u), dict(a))
                            for n, s, u, a in d.get("program", [])],
                   scopes={k: [str(x) for x in v]
                           for k, v in d.get("scopes", {}).items()})

    def to_json(self) -> dict:
        return dict(ops=self.ops, modules=self.modules, host=self.host,
                    program=self.program, scopes=self.scopes)

    def has_scopes(self) -> bool:
        return any(any(v) for v in self.scopes.values())

    def loop_time(self) -> LoopTime:
        if self._loop is None:
            self._loop = _loop_time(self)
        return self._loop


def load(path: str) -> ProgramTrace:
    """``bench.trace.load`` (the benchmark's own reduction, unchanged), plus
    the program's spans with their arguments from the host planes.  The
    ops' scopes are filled in when a step reader first asks
    (:func:`phase_us`)."""
    from jax.profiler import ProfileData

    base = _TRACE_LOAD(path)
    program = [(e.name, int(e.start_ns), int(e.duration_ns), dict(e.stats))
               for plane in ProfileData.from_file(path).planes
               if plane.name.startswith("/host:")
               for line in plane.lines for e in line.events
               if e.name.startswith(PREFIX)]
    return ProgramTrace(ops=base.ops, modules=base.modules,
                        host=base.host + [sp[:3] for sp in program],
                        program=program)


def install() -> None:
    """Have ``bench.trace.load`` read a :class:`ProgramTrace`."""
    trace.load = load


def read_json(path: str) -> ProgramTrace:
    with open(path) as f:
        return ProgramTrace.from_json(json.load(f))


def _loop_intervals(tr: trace.Trace, dev: str, lo: int, hi: int
                    ) -> List[Tuple[int, int]]:
    return sorted((max(s, lo), min(s + d, hi))
                  for n, s, d in tr.modules.get(dev, [])
                  if trace.executable_name(n) in LOOP_EXECUTABLES
                  and s < hi and s + d > lo)


def _loop_time(tr: ProgramTrace) -> LoopTime:
    lo, hi = trace.window(tr)
    total = 0
    leaf: Dict[str, int] = {}
    outer: Dict[str, int] = {}
    for dev, evs in tr.ops.items():
        loops = _loop_intervals(tr, dev, lo, hi)
        total += sum(b - a for a, b in loops)
        if not loops:
            continue
        starts = [a for a, _ in loops]
        scs = tr.scopes.get(dev) or [""] * len(evs)
        inside = []
        for (_, s, d), sc in zip(evs, scs):
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and s < loops[k][1]:
                inside.append((s, -d, sc))
        inside.sort()
        self_ns = [-nd for _, nd, _ in inside]
        parent_of_any = [False] * len(inside)
        stack: List[Tuple[int, int]] = []       # (end, index)
        for i, (s, nd, _) in enumerate(inside):
            while stack and stack[-1][0] <= s:
                stack.pop()
            if stack:
                end, p = stack[-1]
                self_ns[p] -= min(s - nd, end) - s
                parent_of_any[p] = True
            stack.append((s - nd, i))
        for (_, _, sc), own, holds in zip(inside, self_ns, parent_of_any):
            dst = outer if holds else leaf
            dst[sc] = dst.get(sc, 0) + own
    return LoopTime(executables=total, leaf=leaf, outer=outer)


def loop_iterations(run) -> int:
    """The loop iterations ``loop_iter_us`` divides by."""
    return sum(s.iterations for g in run.grids
               for s in lanes.slices(run.graphs, g.specs, g.result.steps))


def phase_us(run, phase: str) -> Optional[float]:
    """Device us per loop iteration of the ops under ``phase``'s scope."""
    tr = run.trace
    # spans and scopes came into the program together: a trace with no
    # spans has no scopes to compile the loop again for
    if not isinstance(tr, ProgramTrace) or not tr.program:
        return None
    iters = loop_iterations(run)
    if iters == 0:
        return None
    if not tr.scopes:                 # as loaded: ask the live executables
        tables = scope_tables(run)
        tr.scopes = {dev: _op_scopes(evs, tr.modules.get(dev, []), tables)
                     for dev, evs in tr.ops.items()}
    if not tr.has_scopes():
        return None
    lt = tr.loop_time()
    if lt.unscoped_share() > MAX_UNSCOPED:
        return None
    return lt.scoped(phase) / 1e3 / iters


def scope_tables(run):
    """:func:`loop_tables`, with the scopes in them.  JAX's persistent
    compile cache keys a program without its metadata, so an executable
    loaded from it may carry the ``op_name`` of the program that compiled
    the entry: the same computation without the scopes.  Then the traced
    grids' loop executables are compiled again, with the metadata in the
    key (``jax_compilation_cache_include_metadata_in_key``), through the
    harness's warm-up: the same chunks, lanes and paddings."""
    tables = loop_tables()
    if any(any(v) for v in tables.values()):
        return tables
    import jax

    from bench import harness

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        configs = {c["name"]: c for c in json.load(f)["configs"]}
    with open(os.path.join(root, configs[run.cell["config"]]["file"])) as f:
        program = harness.Program(json.load(f), [])
    program.graphs = list(run.graphs)
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        jax.clear_caches()
        for g in run.grids:
            program.warm_up(g.cases)
    finally:
        jax.config.update(key, was)
    return loop_tables()


def spans_in_window(tr: trace.Trace, name: str) -> List[Span]:
    if not isinstance(tr, ProgramTrace):
        return []
    lo, hi = trace.window(tr)
    return [sp for sp in tr.program
            if sp[0] == name and sp[1] >= lo and sp[1] + sp[2] <= hi]


def innermost_spans(tr: trace.Trace, lo: int, hi: int
                    ) -> List[Tuple[int, int, str]]:
    """[lo, hi) cut where the host's innermost span changes, each piece
    named as ``trace.span_at`` names a time in it (``"host"``: none)."""
    spans = [(s, s + d, n) for n, s, d in tr.host if n != trace.WINDOW_SPAN]
    cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                              if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        out.append((a, b, trace.span_at(tr.host, a)))
    return out


def idle_host_work_share(tr: trace.Trace) -> Optional[float]:
    """Share of the window, mean over devices, in which the device is idle
    (``trace.gaps``) while the host's innermost span is a program span
    other than :data:`NOT_HOST_WORK`."""
    if not isinstance(tr, ProgramTrace) or not tr.program or not tr.ops:
        return None
    lo, hi = trace.window(tr)
    work = [(a, b) for a, b, n in innermost_spans(tr, lo, hi)
            if n.startswith(PREFIX) and n not in NOT_HOST_WORK]
    shares = []
    for evs in tr.ops.values():
        gaps, i, idle = trace.gaps(evs, lo, hi), 0, 0
        for a, b in work:                 # both sorted and disjoint
            while i < len(gaps) and gaps[i][1] <= a:
                i += 1
            j = i
            while j < len(gaps) and gaps[j][0] < b:
                idle += min(b, gaps[j][1]) - max(a, gaps[j][0])
                j += 1
        shares.append(idle / (hi - lo))
    return sum(shares) / len(shares)
