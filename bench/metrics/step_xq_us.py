"""step_xq_us: device microseconds per loop iteration of the step's work on
the XQueue arrays: the self time of the device ops under the ``xq`` named
scope (the push in ``spawn``, the pop scan in ``dequeue``, NA-WS's
transfer in ``victim``), inside the loop executables, summed over devices,
over the loop iterations ``loop_iter_us`` divides by.

Ops are placed as the phase readers place them (bench.program_trace), with
``xq`` one more scope: an instruction takes the innermost of the phase
scopes and ``xq`` on its ``op_name`` path, and one without an ``op_name``
takes its fused computation's, else its nearest user's, else its nearest
operand's.  ``xq`` nests inside those three phases, so its ops are a part
of theirs.  ``None`` where the program names no ``xq`` scope (a program
older than the scope) or where the scopes were not read (more than
``MAX_UNSCOPED`` of the loops' leaf-op time has no scope)."""

from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import os
import re
from typing import Dict, List, Optional

from bench import program_trace, trace

program_trace.install()

XQ = "xq"
#: the phase readers' scopes, and the XQueue's inside three of them
SCOPES = program_trace.SCOPES + (XQ,)

# the helpers below are bench.program_trace's own (its ``scope_of``,
# ``hlo_table``, ``loop_tables``, ``_op_key``, ``_op_scopes`` and the
# compile of ``scope_tables``), with ``SCOPES`` in place of its scopes
_SCOPE = re.compile(r"(?:^|/)(?:[\w.]+\()*(" + "|".join(SCOPES)
                    + r")\)*(?=/|$)")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?(%\S+) .*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?((%\S+) = \S+) ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=(%[\w.\-]+)")
_OPERAND = re.compile(r"(?<![=\w])(%[\w.\-]+)")


def scope_of(op_name: str) -> str:
    """The innermost of :data:`SCOPES` in an ``op_name`` path, or ``""``."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else ""


def hlo_table(text: str) -> Dict[str, str]:
    """Op key -> scope (one of :data:`SCOPES`) of a module's HLO text."""
    comps: Dict[str, List[str]] = {}
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


def loop_tables() -> Dict[str, List[Dict[str, str]]]:
    """Per loop function, the :func:`hlo_table` of each live executable
    that places an op under ``xq``.  An executable loaded from JAX's
    persistent compile cache, which keys a program without its metadata,
    carries the ``op_name`` of the program that compiled the entry, such
    as one without the scope."""
    from jax.extend import backend

    out: Dict[str, List[Dict[str, str]]] = {}
    for ex in backend.get_backend().live_executables():
        for m in ex.hlo_modules():
            if trace.executable_name(m.name) in \
                    program_trace.LOOP_EXECUTABLES:
                table = hlo_table(m.to_string())
                if XQ in table.values():
                    out.setdefault(m.name, []).append(table)
    return out


def compile_again(run) -> None:
    """The traced grids' loop executables compiled again with the metadata
    in the compile cache's key, through the harness's warm-up: the same
    chunks, lanes and paddings (as ``program_trace.scope_tables`` does)."""
    import jax

    from bench import harness

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
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


def _op_key(name: str) -> str:
    i = name.find(" = ")
    j = name.find(" ", i + 3) if i >= 0 else -1
    return name[:j] if j >= 0 else name


def op_scopes(evs: List[trace.Event], modules: List[trace.Event],
              tables: Dict[str, List[Dict[str, str]]]) -> List[str]:
    """The scope of each op event inside a loop executable's event, from
    the table of the live executable that knows most of its ops' keys."""
    out = [""] * len(evs)
    loops = sorted((s, s + d, n.split("(")[0]) for n, s, d in modules
                   if trace.executable_name(n)
                   in program_trace.LOOP_EXECUTABLES)
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


def xq_ns(tr: program_trace.ProgramTrace, scopes: Dict[str, List[str]]
          ) -> Optional[int]:
    """Self ns of the ops under ``xq`` in the loop executables, summed
    over devices, with ``scopes`` (device -> one of :data:`SCOPES` or
    ``""`` per op event) in place of the phase readers' scopes; ``None``
    where no op is under ``xq`` or the scopes were not read."""
    if not any(XQ in v for v in scopes.values()):
        return None
    lt = dataclasses.replace(tr, scopes=scopes, _loop=None).loop_time()
    if lt.unscoped_share() > program_trace.MAX_UNSCOPED:
        return None
    return lt.scoped(XQ)


def read(run):
    tr = run.trace
    # spans, phase scopes and the xq scope came into the program in that
    # order: a trace with no spans has no scopes to compile the loop for
    if not isinstance(tr, program_trace.ProgramTrace) or not tr.program:
        return None
    iters = program_trace.loop_iterations(run)
    if iters == 0:
        return None
    tables = loop_tables()
    if not tables:
        compile_again(run)
        tables = loop_tables()
    ns = xq_ns(tr, {dev: op_scopes(evs, tr.modules.get(dev, []), tables)
                    for dev, evs in tr.ops.items()})
    return None if ns is None else ns / 1e3 / iters
