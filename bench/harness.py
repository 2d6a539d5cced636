"""One run of one benchmark cell: set-up, the measured window, the check.

Everything a cell needs is found by name from ``BENCHMARK.json`` at the
checkout root: the cell's configuration file (its ``file``), its traffic
mix (``bench/traffic/<traffic>.json``) and one reader per metric
(``bench/metrics/<metric>.py``, a ``read(run)`` returning a number or
``None``).  A cell, a mix or a metric is added by adding files and entries;
nothing here names one.

The run drives the program's ``run_cases`` the way its users call it:
``strategy="auto"`` (vmap on one chip, sharded on four), ``cache=None``,
the default step backend and the default chunk pipeline.  The last line of
standard output is the result as one JSON object.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import List, Optional

from bench import check
from bench.grid import GridSource, expand


@dataclasses.dataclass
class GridRun:
    cases: list           # the grid's case dicts (see bench.grid)
    specs: list           # the same cases as the program's CaseSpecs
    result: object        # the program's SweepResult
    seconds: float        # host clock, call to rows back


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""
    cell: dict
    setup_s: float
    compile_setup_s: float
    compiles_in_window: int
    window_s: float
    grids: List[GridRun]
    graphs: list          # the program's TaskGraphs, in config app order
    trace: Optional[object] = None    # bench.trace.Trace of the window

    @property
    def rows(self) -> int:
        return sum(len(g.cases) for g in self.grids)


class CompileClock:
    """Seconds JAX spends compiling (or loading from its persistent cache),
    and how many compiles it made, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 1


def use_compile_cache(root: str) -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set (JAX reads it itself), else the checkout's fixed ``.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class Program:
    """The system under test, fed with the configuration's inputs."""

    def __init__(self, config: dict, graphs):
        from repro.core.costs import CostModel
        from repro.core.plan import CaseSpec
        from repro.core.spec import RuntimeSpec
        from repro.core.state import SimConfig
        from repro.core.sweep import run_cases
        from repro.core.taskgraph import TaskGraph
        from repro.core.topology import MachineTopology

        self._CaseSpec, self._RuntimeSpec = CaseSpec, RuntimeSpec
        self._TaskGraph = TaskGraph
        self._run_cases = run_cases
        m = config["machine"]
        self.topology = MachineTopology(
            name=m["name"], n_sockets=m["n_sockets"],
            cores_per_socket=m["cores_per_socket"],
            dist=tuple(tuple(r) for r in m["dist"]))
        sim = config["sim"]
        self.cfg = SimConfig(n_workers=config["n_workers"],
                             queue_cap=sim["queue_cap"],
                             stack_cap=sim["stack_cap"],
                             max_steps=sim["max_steps"],
                             costs=CostModel(**config["costs"]))
        self.graphs = [TaskGraph(**g.fields()) for g in graphs]

    def specs(self, cases: list) -> list:
        return [self._CaseSpec(
            spec=self._RuntimeSpec(queue=c["queue"], barrier=c["barrier"],
                                   balance=c["balance"]),
            n_workers=c["n_workers"], seed=c["seed"],
            n_victim=c["n_victim"], n_steal=c["n_steal"],
            t_interval=c["t_interval"], p_local=c["p_local"],
            p_local_node=c["p_local_node"], graph=c["graph"],
            topology=self.topology) for c in cases]

    def run(self, specs: list):
        return self._run_cases(self.graphs, specs, cfg=self.cfg, cache=None)

    def warm_up(self, cases: list) -> None:
        """Run a grid with every case on a one-task stand-in graph: the
        grid's own chunks, lanes and paddings, so every shape the window
        uses compiles (or loads from the cache), at almost no run time."""
        from bench.graphs import stand_in

        stand = self._TaskGraph(**stand_in().fields())
        specs = self.specs([dict(c, graph=len(self.graphs)) for c in cases])
        self._run_cases(self.graphs + [stand], specs, cfg=self.cfg,
                        cache=None)


#: chunks of one grid that a traced run sends: the profiler keeps about
#: 6.2 million device op events, a whole grid makes 7 to 12 million, and
#: stopping the profiler costs about 30 us per event (TPU v5e)
TRACE_CHUNKS = 2


def grid_once(program: Program, source: GridSource, traced: bool
              ) -> GridRun:
    """One grid of the closed loop, or in a traced run the cases of the
    grid's first ``TRACE_CHUNKS`` chunks, under the harness's spans."""
    import jax

    def span(name):
        return (jax.profiler.TraceAnnotation(name) if traced
                else contextlib.nullcontext())

    t0 = time.perf_counter()
    with span("bench.grid_build"):
        cases = source.next()
        if traced:
            from repro.core.plan import build_plan

            chunks = build_plan(program.graphs, program.specs(cases)).chunks
            cases = [cases[i] for c in chunks[:TRACE_CHUNKS]
                     for i in c.indices]
        specs = program.specs(cases)
    with span("bench.run_cases"):
        res = program.run(specs)
    return GridRun(cases=cases, specs=specs, result=res,
                   seconds=time.perf_counter() - t0)


def main(argv=None, *, start: float | None = None, root: str | None = None,
         require_accelerator: bool = True) -> int:
    start = time.perf_counter() if start is None else start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = root or os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        return _fail(f"unknown workload {args.workload!r}; "
                     f"known: {sorted(cells)}")
    cell = cells[args.workload]
    conf_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, conf_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    metrics = [m for m in (bench["per_layer"] if args.trace
                           else bench["end_to_end"])
               if _applies(m, cell["name"])]
    readers = {m["name"]: _load_module(
        os.path.join(root, "bench", "metrics", m["name"] + ".py"),
        "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"))
        for m in metrics}

    sys.path.insert(0, os.path.join(root, "src"))
    try:
        import repro.core.sweep  # noqa: F401
    except ImportError as e:
        return _fail(f"the program is not in this checkout: {e}")
    import jax

    devices = jax.devices()
    if require_accelerator:
        if devices[0].platform == "cpu":
            return _fail("needs an accelerator; JAX found only the CPU")
        if len(devices) != cell["chips"]:
            return _fail(f"cell {cell['name']} needs {cell['chips']} "
                         f"chips; JAX found {len(devices)}")
    cache_dir = use_compile_cache(root)
    clock = CompileClock()

    from bench import graphs as graphs_mod

    graphs = [graphs_mod.build(a, config["graph_seed"])
              for a in config["apps"]]
    program = Program(config, graphs)
    source = GridSource(traffic, config, args.seed)
    program.warm_up(expand(traffic, config, traffic["case_seeds"][0]))
    setup_s = time.perf_counter() - start
    compile_setup_s, n_compiles = clock.seconds, clock.count
    print(f"bench: set-up {setup_s:.2f} s, compile or cache load "
          f"{compile_setup_s:.2f} s, cache {cache_dir}", file=sys.stderr)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None
    grids: List[GridRun] = []
    t0 = time.perf_counter()
    if args.trace:
        # the Python tracer would record every host function call; the
        # harness's own spans are TraceMe level 1
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level, opts.python_tracer_level = 1, 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            grids.append(grid_once(program, source, traced=True))
        window_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
        print(f"bench: trace stopped {time.perf_counter() - t0 - window_s:.1f}"
              f" s after the window", file=sys.stderr)
    else:
        while True:
            grids.append(grid_once(program, source, traced=False))
            if time.perf_counter() - t0 >= args.seconds:
                break
        window_s = time.perf_counter() - t0
    compiles_in_window = clock.count - n_compiles

    used = devices[:cell["chips"]] if require_accelerator else devices
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in used]
    device = dict(platform=devices[0].platform,
                  kind=devices[0].device_kind, count=len(devices),
                  memory_peak_bytes=int(max(peaks)))

    run = Run(cell=cell, setup_s=setup_s, compile_setup_s=compile_setup_s,
              compiles_in_window=compiles_in_window, window_s=window_s,
              grids=grids, graphs=program.graphs)
    breakdown = None
    if args.trace:
        from bench import trace as trace_mod

        t1 = time.perf_counter()
        run.trace = trace_mod.load(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"bench: trace read in {time.perf_counter() - t1:.1f} s",
              file=sys.stderr)
        busy = trace_mod.device_busy(run.trace)
        device["busy_s"] = (sum(busy.values()) / len(busy)) if busy else 0.0
        device["window_s"] = window_s
        breakdown = dict(device_ops=[list(x) for x in
                                     trace_mod.top_ops(run.trace)],
                         idle_gaps=[list(x) for x in
                                    trace_mod.idle_gaps(run.trace)])

    values = {}
    for m in metrics:
        v = readers[m["name"]].read(run)
        if v is not None:
            values[m["name"]] = dict(value=v, unit=m["unit"])

    numbers = check.run(grids, graphs, config,
                        traffic.get("check_rows", 12), args.seed)
    correct = check.passed(numbers)
    attempted = run.rows
    failed = sum(int((~g.result.completed).sum()) for g in grids)
    print(f"bench: {len(grids)} grids, {attempted} rows in {window_s:.3f} s "
          f"(grids of {', '.join(f'{g.seconds:.3f}' for g in grids)} s), "
          f"{compiles_in_window} compiles in the window", file=sys.stderr)
    for name, n in numbers.items():
        print(f"check: {name} {n['value']} (limit {n['limit']})",
              file=sys.stderr)
    out = dict(correct=correct, attempted=attempted, failed=failed,
               metrics=values, device=device)
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = numbers
    print(json.dumps(out))
    return 0
