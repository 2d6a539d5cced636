"""The ``xq`` scope and its reader, ``step_xq_us``.

The step names its three touches of the XQueue arrays with a
``jax.named_scope("xq")`` nested in the phase scopes.  On the HLO of the
loop executable compiled on the CPU: the scope is there, under ``spawn``,
``dequeue`` and ``victim``, and the phase readers place every op as they
would without it.  On ``data/trace_xq_small.json``, a trace recorded on a
TPU v5e (a traced ``epyc192-dlb`` run, trimmed to 25 ms around the end of
the first chunk's loop, op names cut to 100 characters, each op's phase
as the phase readers assigned it under ``scopes`` and its place with
``xq`` among the scopes under ``xq_scopes``): the reader's time is a part
of those three phases' time."""

import dataclasses
import importlib.util
import json
import os
import re

import pytest

from bench import program_trace as pt
from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "metrics")
XQ_PHASES = ("spawn", "dequeue", "victim")


def _reader():
    """``step_xq_us`` loaded as the harness loads it, with ``trace.load``
    put back after (the reader installs the program's loader)."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_step_xq_us", os.path.join(METRICS, "step_xq_us.py"))
    mod = importlib.util.module_from_spec(spec)
    saved = trace.load
    try:
        spec.loader.exec_module(mod)
    finally:
        trace.load = saved
    return mod


class _Run:
    def __init__(self, tr):
        self.trace, self.grids, self.graphs = tr, [], []


@pytest.fixture(scope="module")
def loop_hlo():
    """The HLO text of the ``vmap`` loop executable of a tiny sweep over
    the three balancers."""
    from jax.extend import backend

    from repro.core import taskgraph
    from repro.core.plan import CaseSpec
    from repro.core.scheduler import SimConfig
    from repro.core.spec import RuntimeSpec
    from repro.core.sweep import run_cases

    specs = [CaseSpec(spec=RuntimeSpec(queue="xqueue", barrier="tree",
                                       balance=b), n_workers=8, seed=s,
                      topology="quad_socket_48")
             for b in ("static_rr", "na_ws", "na_rp") for s in (1, 2)]
    run_cases(taskgraph.fib(6), specs,
              cfg=SimConfig(n_workers=8, max_steps=5_000), strategy="vmap")
    texts = [m.to_string() for ex in backend.get_backend().live_executables()
             for m in ex.hlo_modules()
             if trace.executable_name(m.name) == "_batch_body"]
    assert texts
    return texts[-1]


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def _without_xq(s):
    """``s`` with the ``xq`` component taken out of every op_name path."""
    return re.sub(r"/(?:[\w.]+\()*xq\)*(?=/|\")", "", s)


def test_xq_scope_sits_in_three_phases(loop_hlo):
    under = {pt.scope_of(n) for n in _op_names(loop_hlo)
             if re.search(r"(?:^|/)(?:[\w.]+\()*xq\)*(?=/|$)", n)}
    assert under == set(XQ_PHASES)


def test_phases_place_every_op_as_without_the_scope(loop_hlo):
    names = _op_names(loop_hlo)
    stripped = _without_xq(loop_hlo)
    assert stripped != loop_hlo
    assert "xq" not in {c for n in _op_names(stripped) for c in
                        re.split(r"[/()]", n)}
    assert [pt.scope_of(n) for n in names] == \
        [pt.scope_of(n) for n in _op_names(stripped)]
    assert pt.hlo_table(loop_hlo) == pt.hlo_table(stripped)


def test_reader_table_refines_the_phase_table(loop_hlo):
    """The reader places each op as the phase readers do, except that an
    op of spawn, dequeue or victim may be placed under ``xq``; without the
    scope in the HLO the two tables are equal."""
    xq = _reader()
    phases, mine = pt.hlo_table(loop_hlo), xq.hlo_table(loop_hlo)
    assert phases.keys() == mine.keys()
    moved = {k for k in mine if mine[k] != phases[k]}
    assert moved and all(mine[k] == "xq" and phases[k] in XQ_PHASES
                         for k in moved)
    assert {phases[k] for k in moved} == set(XQ_PHASES)
    assert xq.hlo_table(_without_xq(loop_hlo)) == phases


@pytest.fixture(scope="module")
def chip():
    with open(os.path.join(DATA, "trace_xq_small.json")) as f:
        d = json.load(f)
    return pt.ProgramTrace.from_json(d), d["xq_scopes"]


def test_xq_time_is_a_part_of_three_phases_on_chip(chip):
    tr, xq_scopes = chip
    xq = _reader()
    lt = tr.loop_time()
    got = xq.xq_ns(tr, xq_scopes)
    assert got is not None and got > 0
    assert got <= sum(lt.scoped(p) for p in XQ_PHASES)
    # op by op: under xq only ops of the three phases, the rest in place
    for dev, evs in tr.ops.items():
        for phase, mine in zip(tr.scopes[dev], xq_scopes[dev]):
            assert mine == phase or (mine == "xq" and phase in XQ_PHASES)
    # the phases the reader leaves lose what it moved to xq, no more
    lx = dataclasses.replace(tr, scopes=xq_scopes, _loop=None).loop_time()
    assert sum(lt.scoped(p) for p in XQ_PHASES) == \
        sum(lx.scoped(p) for p in XQ_PHASES) + got
    assert lx.unscoped_share() == lt.unscoped_share()


def test_reader_is_none_without_the_scope(chip):
    """A program older than the scope (the phase scopes alone), and a
    trace of a program with no spans or scopes: ``None``."""
    tr, _ = chip
    xq = _reader()
    assert xq.xq_ns(tr, tr.scopes) is None
    small = os.path.join(DATA, "trace_small.json")
    assert xq.read(_Run(trace.read_json(small))) is None
    assert xq.read(_Run(pt.read_json(small))) is None


def test_stale_executables_are_compiled_again(monkeypatch, chip):
    """Where no live loop executable names ``xq`` (entries of JAX's
    persistent cache, keyed without metadata, compiled from a program
    without the scope), the reader compiles the traced grid's loop
    executables again and finds it."""
    from bench import graphs as graphs_mod
    from bench.grid import GridSource
    from bench.harness import GridRun, Program
    from bench.tests.helpers import REPO, tiny_config

    config = tiny_config("quad48-bots")
    with open(os.path.join(REPO, "bench", "traffic", "lattice.json")) as f:
        cases = GridSource(json.load(f), config, 7).next()[:12]
    program = Program(config, [graphs_mod.build(a, config["graph_seed"])
                               for a in config["apps"]])
    specs = program.specs(cases)
    run = _Run(chip[0])
    run.cell = dict(config="quad48-bots")
    run.graphs = program.graphs
    run.grids = [GridRun(cases=cases, specs=specs,
                         result=program.run(specs), seconds=0.0)]
    xq = _reader()
    real_tables, real_compile, calls = xq.loop_tables, xq.compile_again, []

    def stale_tables():
        calls.append("tables")
        return real_tables() if "compile" in calls else {}

    def compile_again(run):
        calls.append("compile")
        real_compile(run)

    monkeypatch.setattr(xq, "loop_tables", stale_tables)
    monkeypatch.setattr(xq, "compile_again", compile_again)
    xq.read(run)
    assert calls == ["tables", "compile", "tables"]
    assert any(xq.XQ in t.values() for t in real_tables()["jit__batch_body"])
