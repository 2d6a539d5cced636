"""The reduction of the program's spans and scopes (bench.program_trace)
and the readers of its metrics, against brute-force counts over a
one-bit-per-ns map: on a small synthetic trace, and on
``data/trace_program_small.json``, a trace recorded on a TPU v5e (a traced
``quad48-lattice`` run, trimmed to 25 ms around the end of the first
chunk's loop, op names cut to 100 characters, each op's scope as the step
readers assigned it).  Every new reader returns ``None`` on a trace of a
program without spans or scopes (the chip trace of ``test_trace.py``)."""

import importlib.util
import json
import os

import numpy as np
import pytest

from bench import program_trace as pt
from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "metrics")
NEW_METRICS = tuple(f"step_{p}_us" for p in pt.PHASES) + (
    "submit_ms", "idle_host_work_share")


def _reader(name):
    """A reader loaded as the harness loads it, with ``trace.load`` put
    back after (the reader installs the program's loader)."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name, os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    saved = trace.load
    try:
        spec.loader.exec_module(mod)
    finally:
        trace.load = saved
    return mod


class _Run:
    """What a reader sees of a run: the trace, and no grids."""

    def __init__(self, tr):
        self.trace, self.grids, self.graphs = tr, [], []


def brute_self(tr):
    """(scope -> self ns of leaves, scope -> self ns of the other ops,
    executables' ns) inside the loop executables: each ns belongs to the
    innermost op over it, painted from the outermost in."""
    lo, hi = trace.window(tr)
    leaf, outer, total = {}, {}, 0
    for dev, evs in tr.ops.items():
        in_loop = np.zeros(hi - lo, bool)
        for n, s, d in tr.modules[dev]:
            if trace.executable_name(n) in pt.LOOP_EXECUTABLES:
                in_loop[max(s, lo) - lo:max(min(s + d, hi) - lo, 0)] = True
        total += int(in_loop.sum())
        scs = tr.scopes.get(dev) or [""] * len(evs)
        mine = sorted((s, -d, i) for i, (_, s, d) in enumerate(evs)
                      if lo <= s < hi and in_loop[s - lo])
        owner = np.full(hi - lo, -1, np.int32)
        for s, nd, i in mine:
            owner[s - lo:min(s - nd, hi) - lo] = i
        counts = np.bincount(owner[owner >= 0], minlength=len(evs))
        for k, (s, nd, i) in enumerate(mine):
            holds = False
            for t, u, _ in mine[k + 1:]:     # by start: stop past the end
                if t >= s - nd or t - u <= s - nd:
                    holds = t < s - nd
                    break
            dst = outer if holds else leaf
            dst[scs[i]] = dst.get(scs[i], 0) + int(counts[i])
    return leaf, outer, total


def brute_idle_host_work(tr):
    lo, hi = trace.window(tr)
    names = ["host"] + sorted({n for n, _, _ in tr.host})
    code = np.zeros(hi - lo, np.int32)
    for n, s, d in sorted(tr.host, key=lambda e: e[1]):
        if n != trace.WINDOW_SPAN:
            code[max(s, lo) - lo:max(min(s + d, hi) - lo, 0)] = \
                names.index(n)
    work = np.array([n.startswith(pt.PREFIX) and n not in pt.NOT_HOST_WORK
                     for n in names])[code]
    shares = []
    for evs in tr.ops.values():
        busy = np.zeros(hi - lo, bool)
        for _, s, d in evs:
            busy[max(s, lo) - lo:max(min(s + d, hi) - lo, 0)] = True
        shares.append(int((work & ~busy).sum()) / (hi - lo))
    return sum(shares) / len(shares)


def synthetic():
    """One device, a window of 1,000 ns: an init executable, then a loop
    executable whose outer while holds a spawn fusion, the thief's retry
    while (holding a fusion), an exec fusion and an unscoped copy."""
    args = dict(call=1, chunk=0, lanes=6, padded=8)
    program = [("repro.run_cases", 10, 980, dict(call=1, rows=6, chunks=1)),
               ("repro.submit", 20, 120, args),
               ("repro.stack", 20, 70, args),
               ("repro.init", 90, 20, args),
               ("repro.dispatch", 110, 30, args),
               ("repro.collect", 140, 560, args),
               ("repro.wait", 140, 430, args),
               ("repro.fetch", 570, 130, args),
               ("repro.postprocess", 700, 90, args),
               ("repro.finish", 900, 50, dict(call=1))]
    host = [("bench.window", 0, 1000), ("bench.run_cases", 0, 1000)] + [
        sp[:3] for sp in program]
    ops = [("init-fusion", 100, 20, ""), ("while.1", 150, 400, ""),
           ("fusion.spawn", 160, 40, "spawn"), ("while.2", 210, 90, "thief"),
           ("fusion.thief", 220, 40, "thief"),
           ("fusion.thief2", 270, 25, "thief"),
           ("fusion.exec", 310, 90, "exec"), ("copy", 400, 10, ""),
           ("gate-fusion", 520, 20, "gate"), ("fetch-copy", 580, 30, "")]
    return pt.ProgramTrace(
        ops={"/device:TPU:0": [o[:3] for o in ops]},
        modules={"/device:TPU:0": [("jit__init_body(2)", 100, 20),
                                   ("jit__batch_body(1)", 150, 400)]},
        host=host, program=program,
        scopes={"/device:TPU:0": [o[3] for o in ops]})


def _trace(name):
    if name == "synthetic":
        return synthetic()
    return pt.read_json(os.path.join(DATA, "trace_program_small.json"))


TRACES = ["synthetic", "chip"]


def test_synthetic_loop_time():
    lt = synthetic().loop_time()
    assert lt.scoped("thief") == 90 and lt.scoped("spawn") == 40
    assert lt.outer == {"": 150, "thief": 25}


@pytest.mark.parametrize("name", TRACES)
def test_loop_self_time_agrees_with_brute_force(name):
    tr = _trace(name)
    lt = tr.loop_time()
    assert (lt.leaf, lt.outer, lt.executables) == brute_self(tr)
    # the phases' self time plus the unscoped rest is the loop
    # executables' time: every ns of them is one op's self time or a gap
    # between the outer ops
    lo, hi = trace.window(tr)
    gaps = sum(b - a for dev, evs in tr.ops.items()
               for a, b in _gaps_in_loops(tr, dev, evs, lo, hi))
    assert sum(lt.scoped(sc) for sc in pt.SCOPES) + lt.leaf.get("", 0) \
        + lt.outer.get("", 0) + gaps == lt.executables > 0
    assert any(lt.scoped(p) > 0 for p in pt.PHASES)


def _gaps_in_loops(tr, dev, evs, lo, hi):
    for a, b in pt._loop_intervals(tr, dev, lo, hi):
        yield from trace.gaps([e for e in evs if a <= e[1] < b], a, b)


@pytest.mark.parametrize("name", TRACES)
def test_idle_host_work_share_agrees_with_brute_force(name):
    tr = _trace(name)
    got = pt.idle_host_work_share(tr)
    assert got == pytest.approx(brute_idle_host_work(tr), abs=1e-12)
    assert 0 < got < 1


@pytest.mark.parametrize("name", TRACES)
def test_gaps_are_named_by_program_or_harness_spans(name):
    gaps = trace.idle_gaps(_trace(name))
    assert gaps and all(n.startswith(("repro.", "bench.")) for n, _ in gaps)
    assert any(n.startswith("repro.") for n, _ in gaps)


def test_json_round_trip():
    tr = synthetic()
    back = pt.ProgramTrace.from_json(json.loads(json.dumps(tr.to_json())))
    assert back == tr and back.loop_time() == tr.loop_time()


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_batch_body)/while/body/vmap(spawn)/while/body/ge", "spawn"),
    ("jit(_batch_body)/while/body/vmap(thief)/while/body/jit(_where)",
     "thief"),
    ("jit(_run_batch_sharded)/shard_map/while/body/vmap(exec)/add", "exec"),
    ("jit(_batch_body)/while/body/vmap(gate)/reduce_or", "gate"),
    ("jit(_batch_body)/while/body/vmap()/convert_element_type", ""),
    ("jit(_batch_body)/while/body/vmap(spawnx)/add", ""),
    ("reduce_or", ""),
])
def test_scope_of(op_name, scope):
    assert pt.scope_of(op_name) == scope


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_is_silent_without_program_spans(name):
    """The chip trace of a program with no spans or scopes: every new
    reader returns None, under the plain and the program's reduction."""
    small = os.path.join(DATA, "trace_small.json")
    reader = _reader(name)
    assert reader.read(_Run(trace.read_json(small))) is None
    assert reader.read(_Run(pt.read_json(small))) is None


def test_install_keeps_the_harness_reduction():
    """``install`` swaps the loader, and the program's trace is still a
    ``Trace`` to every existing function."""
    saved = trace.load
    try:
        pt.install()
        assert trace.load is pt.load
    finally:
        trace.load = saved
    tr = synthetic()
    assert isinstance(tr, trace.Trace)
    assert trace.idle_gaps(tr)[0][0].startswith("repro.")


def test_scopes_come_from_the_live_executables_hlo(tmp_path):
    """After a tiny traced ``run_cases`` on the CPU, the loop executable's
    HLO maps its instructions to all eight scopes, and the program's
    spans come back from the trace with their arguments."""
    import jax

    from repro.core import taskgraph
    from repro.core.plan import CaseSpec
    from repro.core.scheduler import SimConfig
    from repro.core.spec import RuntimeSpec
    from repro.core.sweep import run_cases

    g, cfg = taskgraph.fib(6), SimConfig(n_workers=8, max_steps=5_000)
    specs = [CaseSpec(spec=RuntimeSpec(queue="xqueue", barrier="tree",
                                       balance=b), n_workers=8, seed=s)
             for b in ("static_rr", "na_ws", "na_rp") for s in (1, 2)]
    jax.profiler.start_trace(str(tmp_path))
    run_cases(g, specs, cfg=cfg, strategy="vmap")
    jax.profiler.stop_trace()
    tables = pt.loop_tables()["jit__batch_body"]
    assert any(set(t.values()) == set(pt.SCOPES) for t in tables)
    path = trace.find_xplane(str(tmp_path))
    tr = pt.load(path)
    submits = [a for n, _, _, a in tr.program if n == "repro.submit"]
    assert [a["chunk"] for a in submits] == [0, 1, 2]
    # the benchmark's own reduction, with the program's spans added
    base = pt._TRACE_LOAD(path)
    assert (tr.ops, tr.modules) == (base.ops, base.modules)
    assert tr.host == base.host + [sp[:3] for sp in tr.program]


def test_op_scopes_pick_the_executable_that_ran():
    """Two live executables of one loop function (8 and 64 lanes, the
    same instruction names): each loop event's ops take the scopes of the
    one whose op keys they match."""
    def hlo(lanes, first, second):
        return pt.hlo_table(
            f"ENTRY %main.1 (p: s32[{lanes}]) -> s32[{lanes}] {{\n"
            f"  %fusion.1 = s32[{lanes}]{{0}} fusion(s32[{lanes}]{{0}} %p), "
            f'kind=kLoop, metadata={{op_name="jit(_batch_body)/while/body/'
            f'vmap({first})/add" stack_frame_id=3}}\n'
            f"  ROOT %while.2 = (s32[{lanes}]{{0}}, s32[]) while(%t), "
            f'metadata={{op_name="jit(_batch_body)/while/body/'
            f'vmap({second})/while"}}\n}}\n')

    eight, sixty_four = hlo(8, "spawn", "thief"), hlo(64, "exec", "victim")
    tables = {"jit__batch_body": [eight, sixty_four]}
    modules = [("jit__batch_body(7)", 0, 100),
               ("jit__batch_body(9)", 200, 100),
               ("jit__init_body(3)", 400, 50)]
    evs = [("%fusion.1 = s32[64]{0} fusion(s32[64]{0} %p)", 10, 5),
           ("%while.2 = (s32[64]{0}, s32[]) while(%t)", 20, 50),
           ("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p)", 210, 5),
           ("%copy.3 = s32[8]{0} copy(s32[8]{0} %p)", 220, 5),
           ("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p)", 410, 5)]
    assert pt._op_scopes(evs, modules, tables) == [
        "exec", "victim", "spawn", "", ""]


def test_stale_cached_executables_are_compiled_again(monkeypatch):
    """Where the live loop executables carry no scopes (a persistent cache
    entry compiled from the program without them), ``scope_tables``
    compiles the traced grid's loop executables again and finds them."""
    from bench import graphs as graphs_mod
    from bench.grid import GridSource
    from bench.harness import GridRun, Program
    from bench.tests.helpers import REPO, tiny_config

    config = tiny_config("quad48-bots")
    with open(os.path.join(REPO, "bench", "traffic", "lattice.json")) as f:
        cases = GridSource(json.load(f), config, 7).next()
    program = Program(config, [graphs_mod.build(a, config["graph_seed"])
                               for a in config["apps"]])
    specs = program.specs(cases)
    run = _Run(None)
    run.cell = dict(config="quad48-bots")
    run.graphs = program.graphs
    run.grids = [GridRun(cases=cases, specs=specs,
                         result=program.run(specs), seconds=0.0)]
    real, calls = pt.loop_tables, []

    def stale():
        calls.append(1)
        got = real()
        return got if len(calls) > 1 else {
            k: [{} for _ in v] for k, v in got.items()}

    monkeypatch.setattr(pt, "loop_tables", stale)
    tables = pt.scope_tables(run)
    assert len(calls) == 2
    assert any(set(t.values()) == set(pt.SCOPES)
               for t in tables["jit__batch_body"])


def test_hlo_table_places_instructions_without_op_name():
    """A fusion whose root lost its op_name takes its fused computation's
    scope, a layout reshape its user's, and a while keeps its own."""
    text = """HloModule jit__batch_body

%fused_computation.88 (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  %add.1 = s32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(_batch_body)/while/body/vmap(thief)/add"}
  ROOT %scatter.2 = s32[8]{0} scatter(%add.1, %param_0, %param_0), to_apply=%region_1
}

%body.3 (p: (s32[8], s32[8])) -> (s32[8], s32[8]) {
  %p = (s32[8]{0}, s32[8]{0}) parameter(0)
  %gte.4 = s32[8]{0} get-tuple-element(%p), index=0
  %fusion.5 = s32[8]{0} fusion(%gte.4), kind=kCustom, calls=%fused_computation.88
  %reshape.6 = s32[2,4]{1,0} reshape(%fusion.5)
  %negate.7 = s32[2,4]{1,0} negate(%reshape.6), metadata={op_name="jit(_batch_body)/while/body/vmap(exec)/neg"}
  ROOT %tuple.8 = (s32[8]{0}, s32[8]{0}) tuple(%fusion.5, %fusion.5)
}

ENTRY %main.9 (a: s32[8]) -> (s32[8], s32[8]) {
  %a = s32[8]{0} parameter(0)
  %t = (s32[8]{0}, s32[8]{0}) tuple(%a, %a)
  ROOT %while.10 = (s32[8]{0}, s32[8]{0}) while(%t), condition=%cond.11, body=%body.3, metadata={op_name="jit(_batch_body)/while"}
}
"""
    table = pt.hlo_table(text)
    assert table["%fusion.5 = s32[8]{0}"] == "thief"
    assert table["%reshape.6 = s32[2,4]{1,0}"] == "exec"
    assert table["%negate.7 = s32[2,4]{1,0}"] == "exec"
    assert not any(k.startswith("%while.10 ") for k in table)


@pytest.mark.parametrize("unscoped,reads", [(0.0, True), (0.05, True),
                                            (0.2, False), (1.0, False)])
def test_step_readers_need_the_scopes_read(monkeypatch, unscoped, reads):
    """On the chip trace with a share of its loop ops' scopes taken away
    (as when the table of another executable is read): the step readers
    return None once more than ``MAX_UNSCOPED`` of the leaf-op time has no
    scope, and a number below it."""
    tr = _trace("chip")
    dev, = tr.ops
    lt = tr.loop_time()
    total = sum(lt.leaf.values())
    scopes, dropped = list(tr.scopes[dev]), 0
    for i in sorted(range(len(scopes)), key=lambda i: tr.ops[dev][i][2]):
        if dropped >= unscoped * total:
            break
        if scopes[i] and not tr.ops[dev][i][0].startswith("%while."):
            dropped += tr.ops[dev][i][2]
            scopes[i] = ""
    tr.scopes = {dev: scopes}
    tr._loop = None
    monkeypatch.setattr(pt, "loop_iterations", lambda run: 100)
    share = tr.loop_time().unscoped_share()
    assert (share <= pt.MAX_UNSCOPED) == reads
    got = [pt.phase_us(_Run(tr), p) for p in pt.PHASES]
    assert all(v is not None for v in got) if reads else \
        all(v is None for v in got)


def test_step_readers_compile_nothing_without_program_spans(monkeypatch):
    """A trace with no program spans is of a program with no scopes: the
    step readers return None without compiling the loop again."""
    tr = _trace("chip")
    tr.program, tr.scopes = [], {}
    monkeypatch.setattr(pt, "loop_iterations", lambda run: 100)
    monkeypatch.setattr(pt, "scope_tables", lambda run: 1 / 0)
    assert all(pt.phase_us(_Run(tr), p) is None for p in pt.PHASES)


def test_sharded_loop_tables_name_every_scope():
    """The ``sharded`` executor's loop over 4 virtual CPU devices: its live
    executable's HLO maps instructions to all eight scopes, under the name
    the four-chip cell's trace gives it."""
    import subprocess
    import sys

    from bench.tests.helpers import REPO

    code = f"""import sys; sys.path[:0] = [{REPO!r}, {os.path.join(REPO, 'src')!r}]
from bench import program_trace as pt
from repro.core import taskgraph
from repro.core.plan import CaseSpec
from repro.core.scheduler import SimConfig
from repro.core.spec import RuntimeSpec
from repro.core.sweep import run_cases
specs = [CaseSpec(spec=RuntimeSpec(queue="xqueue", barrier="tree", balance=b),
                  n_workers=8, seed=s)
         for b in ("static_rr", "na_ws", "na_rp") for s in (1, 2)]
run_cases(taskgraph.fib(6), specs, cfg=SimConfig(n_workers=8, max_steps=5_000),
          strategy="sharded")
tables = pt.loop_tables()["jit__run_batch_sharded"]
assert any(set(t.values()) == set(pt.SCOPES) for t in tables), tables
print("ok")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0 and p.stdout.strip().endswith("ok"), p.stderr
