"""The engine's own instrumentation: host spans on the profiler's clock
and named scopes on the step's phases.

* Under a profiler trace, ``run_cases`` writes one ``repro.run_cases``
  span per call and, per chunk, one ``repro.submit`` (holding
  ``repro.stack``, ``repro.init`` and ``repro.dispatch``; ``serial``
  dispatches each case's init with its run, so it has no ``repro.init``), one
  ``repro.collect`` (holding ``repro.wait`` and ``repro.fetch``) and one
  ``repro.postprocess``, each carrying the call's and the chunk's
  identifiers; on every executor, the sharded one over 4 virtual devices.
* The lowered batched loop names all eight scopes of the step.
"""

import glob
import os
import re
import subprocess
import sys
import tempfile

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHUNK_SPANS = ("repro.submit", "repro.stack", "repro.init", "repro.dispatch",
               "repro.collect", "repro.wait", "repro.fetch",
               "repro.postprocess")
CHILDREN = {"repro.submit": ("repro.stack", "repro.init", "repro.dispatch"),
            "repro.collect": ("repro.wait", "repro.fetch")}
SCOPES = ("adopt", "spawn", "dequeue", "thief", "victim", "exec", "gate",
          "occupancy")


def _specs():
    from repro.core.plan import CaseSpec
    from repro.core.spec import RuntimeSpec

    return [CaseSpec(spec=RuntimeSpec(queue="xqueue", barrier="tree",
                                      balance=b), n_workers=8, seed=s)
            for b in ("static_rr", "na_ws", "na_rp") for s in (1, 2)]


def traced_spans(strategy: str) -> list:
    """(name, start, end, args) of every ``repro.`` span of one traced
    ``run_cases`` call."""
    import jax
    from jax.profiler import ProfileData

    from repro.core import taskgraph
    from repro.core.scheduler import SimConfig
    from repro.core.sweep import run_cases

    g = taskgraph.fib(6)
    cfg = SimConfig(n_workers=8, max_steps=5_000)
    run_cases(g, _specs(), cfg=cfg, strategy=strategy)    # compile first
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        run_cases(g, _specs(), cfg=cfg, strategy=strategy)
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        pd = ProfileData.from_file(path)
        return [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats))
                for p in pd.planes if p.name.startswith("/host:")
                for line in p.lines for e in line.events
                if e.name.startswith("repro.")]


def check_spans(strategy: str) -> None:
    spans = traced_spans(strategy)

    def inside(a, b):
        return b[1] <= a[1] and a[2] <= b[2]

    root, = [s for s in spans if s[0] == "repro.run_cases"]
    call = root[3]["call"]
    assert root[3]["rows"] == 6 and root[3]["chunks"] == 3
    for name in ("repro.plan", "repro.finish"):
        sp, = [s for s in spans if s[0] == name]
        assert sp[3]["call"] == call and inside(sp, root)
    padded = 4 if strategy == "sharded" else 2
    children = dict(CHILDREN)
    names = CHUNK_SPANS
    if strategy == "serial":
        children["repro.submit"] = ("repro.stack", "repro.dispatch")
        names = tuple(n for n in CHUNK_SPANS if n != "repro.init")
        assert not [s for s in spans if s[0] == "repro.init"]
    for k in range(3):
        for name in names:
            assert len([s for s in spans if s[0] == name
                        and s[3].get("chunk") == k]) == 1, (name, k)
        mine = {s[0]: s for s in spans if s[3].get("chunk") == k}
        for s in mine.values():
            assert inside(s, root)
            assert s[3] == dict(call=call, chunk=k, lanes=2, padded=padded)
        for parent, kids in children.items():
            for kid in kids:
                assert inside(mine[kid], mine[parent])
        starts = [mine[n][1] for n in children["repro.submit"]]
        assert starts == sorted(starts)
        assert mine["repro.submit"][2] <= mine["repro.collect"][1]
        assert mine["repro.collect"][2] <= mine["repro.postprocess"][1]


@pytest.mark.parametrize("strategy", ["vmap", "serial"])
def test_chunk_spans_one_device(strategy):
    check_spans(strategy)


def test_chunk_spans_sharded_four_devices():
    code = ("import sys; sys.path[:0] = [%r, %r]; "
            "from tests.test_spans import check_spans; "
            "check_spans('sharded'); print('ok')"
            % (REPO, os.path.join(REPO, "src")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0 and p.stdout.strip().endswith("ok"), p.stderr


@pytest.fixture(scope="module")
def loop_locations():
    """The scope paths in the lowered batched loop's debug locations."""
    from repro.core import executors, taskgraph
    from repro.core.plan import build_plan
    from repro.core.scheduler import SimConfig, graph_arrays

    g = taskgraph.fib(6)
    cfg = SimConfig(n_workers=8, max_steps=1_000)
    specs = _specs()[:2]
    plan = build_plan([g], specs)
    ctx = executors.ExecContext(cfg=cfg, gq_cap=plan.gq_cap, graphs=[g],
                                garr=[graph_arrays(g, plan.t_pad, xp=np)])
    gb, cb = executors._stack_chunk(ctx, specs, 2)
    st = executors._init_batch(cfg, plan.gq_cap, gb, cb)
    text = executors._run_batch.lower(cfg, plan.gq_cap, gb, cb, st).as_text(
        debug_info=True)
    return set(re.findall(r'loc\("(jit\(_batch_body\)[^"]*)"', text))


@pytest.mark.parametrize("scope", SCOPES)
def test_loop_names_scope(loop_locations, scope):
    pat = re.compile(r"/(?:\w+\()?%s\)?/" % scope)
    assert any(pat.search(loc) for loc in loop_locations), scope
