# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness: every paper table/figure plus the beyond-paper MoE
balance study, the roofline aggregation, the DLB autotuner, and the full
RuntimeSpec ablation lattice.

    PYTHONPATH=src python -m benchmarks.run               # all suites
    PYTHONPATH=src python -m benchmarks.run <suite> ...   # a subset
    PYTHONPATH=src python -m benchmarks.run --list        # suites, grouped
                                                          # by spec axes
    PYTHONPATH=src python -m benchmarks.run \\
        --spec queue=xqueue,barrier=tree,balance=na_ws    # only suites
                                                          # covering a spec
    PYTHONPATH=src python -m benchmarks.run \\
        --backend pallas <suite> ...                      # run on a step
                                                          # backend (default
                                                          # reference)
    PYTHONPATH=src python -m benchmarks.run \\
        --profile <suite> ...                             # jax.profiler trace
                                                          # + engine dispatch
                                                          # stats for the run
    PYTHONPATH=src python -m benchmarks.run cache stats   # result-cache info
    PYTHONPATH=src python -m benchmarks.run cache clear   # drop cached results
    PYTHONPATH=src python -m benchmarks.run \\
        cache clear --version runtime-spec-v1             # prune one stale
                                                          # code-version only
"""

import importlib
import os
import sys
import time

# RuntimeSpec axis values, spelled out here so --list/--spec answer without
# importing jax (keep in sync with repro.core.spec — test_spec asserts it)
AXIS_VALUES = dict(
    queue=("locked_global", "xqueue"),
    barrier=("centralized_count", "tree"),
    balance=("static_rr", "na_rp", "na_ws"),
)

# step-backend names, spelled out for the same no-jax reason (keep in sync
# with repro.core.backends.BACKENDS — test_backends asserts it)
BACKEND_VALUES = ("reference", "pallas", "pallas_fused")

_Q, _B, _L = AXIS_VALUES["queue"], AXIS_VALUES["barrier"], \
    AXIS_VALUES["balance"]

#: suite name -> (description, swept spec-axis values).  ``axes`` records
#: which RuntimeSpec axis values each suite touches: --list groups by the
#: axes a suite *varies* and --spec filters on value coverage.  Import
#: stays lazy so --list and the cache subcommand answer without
#: initializing jax.
SUITES = {
    "ablation_lattice": dict(
        desc="full 2x2x3 RuntimeSpec lattice on all executors + per-axis "
             "speedup attribution (BENCH_sweep.json)",
        axes=dict(queue=_Q, barrier=_B, balance=_L)),
    "numa_ablation": dict(
        desc="lattice x machine topologies (flat vs dual/quad socket) on "
             "all executors + both backends; per-topology attribution "
             "(BENCH_sweep.json, gated by check_regression.py)",
        axes=dict(queue=_Q, barrier=_B, balance=_L)),
    "streaming_slo": dict(
        desc="open-system streaming — lattice x topologies x Poisson "
             "offered loads (arrivals axis) on all executors + both "
             "backends; p50/p90/p99 + throughput-vs-load curves "
             "(BENCH_sweep.json, gated by check_regression.py)",
        axes=dict(queue=_Q, barrier=_B, balance=_L)),
    "moe_serving": dict(
        desc="model-stack workload apps (repro.apps) — MoE expert "
             "dispatch at Zipf skews + continuous-batching decode, "
             "closed lattice x topologies and the decode service under "
             "Poisson loads, on all executors + both backends "
             "(BENCH_sweep.json, gated by check_regression.py)",
        axes=dict(queue=_Q, barrier=_B, balance=_L)),
    "cluster_scaling": dict(
        desc="cluster tier — the machine ladder (flat -> dual socket -> "
             "2-node -> 4-node rack) under per-task payloads on all "
             "executors + all three backends, bandwidth-starvation and "
             "steal-locality curves (BENCH_sweep.json, gated by "
             "check_regression.py)",
        axes=dict(queue=("xqueue",), barrier=("tree",),
                  balance=("na_rp", "na_ws"))),
    "bots_speedup": dict(
        desc="Fig. 4/5 — per-mode makespans + XGOMP(TB) speedups",
        axes=dict(queue=_Q, barrier=_B, balance=("static_rr",))),
    "thread_scaling": dict(
        desc="Fig. 6 — makespan vs worker count, gomp vs xgomptb",
        axes=dict(queue=_Q, barrier=_B, balance=("static_rr",))),
    "posp_throughput": dict(
        desc="Fig. 8 — proof-of-space hashing throughput",
        axes=dict(queue=_Q, barrier=_B, balance=("static_rr",))),
    "dlb_best": dict(
        desc="Fig. 7 + Tables I-III — best NA-RP/NA-WS vs SLB (§V counters)",
        axes=dict(queue=("xqueue",), barrier=("tree",), balance=_L)),
    "timeline": dict(
        desc="Fig. 3 — per-worker utilization timelines",
        axes=dict(queue=("xqueue",), barrier=("tree",), balance=_L)),
    "param_sweep": dict(
        desc="Figs. 9/10 + Table IV — DLB improvement over the knob grid",
        axes=dict(queue=("xqueue",), barrier=("tree",), balance=_L)),
    "guidelines": dict(
        desc="Fig. 11 — guideline settings vs per-app best",
        axes=dict(queue=("xqueue",), barrier=("tree",), balance=_L)),
    "sweep_bench": dict(
        desc="engine timing — serial vs batched vs warm-cache re-run",
        axes=dict(queue=("xqueue",), barrier=("tree",), balance=_L)),
    "step_backends": dict(
        desc="step-backend throughput — reference jnp vs pallas kernels vs "
             "the fused megakernel, plus engine pipeline speedup (bitwise "
             "asserted; BENCH_sweep.json, gated by check_regression.py)",
        axes=dict(queue=("xqueue",), barrier=("tree",),
                  balance=("static_rr", "na_ws"))),
    "tune": dict(
        desc="DLB autotuner — per-(app, spec) artifacts under "
             "experiments/tuned/ (not in the no-args run: it writes "
             "artifacts dlb_best then prefers, which would make "
             "back-to-back full runs differ)",
        axes=dict(queue=("xqueue",), barrier=("tree",),
                  balance=("na_rp", "na_ws"))),
    "moe_balance": dict(
        desc="beyond-paper — DLB policies as MoE-routing balancers "
             "(moe_serving carries the same router stats per skew at "
             "graph-extraction level)",
        axes=None),
    "roofline": dict(
        desc="aggregation — counter-derived roofline summary",
        axes=None),
}

#: suites whose module name differs from the suite name
_MODULES = {"tune": "tune_apps"}

#: excluded from the no-args everything run; invoke explicitly
_EXPLICIT_ONLY = {"tune"}


def _suite_fn(name):
    mod = importlib.import_module(f"benchmarks.{_MODULES.get(name, name)}")
    return mod.run


def _varied_axes(axes):
    """The spec axes a suite actually sweeps (>1 value)."""
    if axes is None:
        return ()
    return tuple(a for a in ("queue", "barrier", "balance")
                 if len(axes.get(a, ())) > 1)


def _list_suites() -> None:
    """Print suites grouped by the spec axes they vary."""
    groups = {}
    for name, info in SUITES.items():
        groups.setdefault(_varied_axes(info["axes"]), []).append(name)
    width = max(map(len, SUITES))
    for varied in sorted(groups, key=lambda v: (-len(v), v)):
        if varied:
            print(f"[sweeps {' x '.join(varied)}]")
        else:
            print("[fixed spec / no spec axes]")
        for name in groups[varied]:
            print(f"  {name:<{width}}  {SUITES[name]['desc']}")
        print()


def parse_spec_filter(arg: str) -> dict:
    """Parse ``queue=xqueue,barrier=tree,balance=na_ws`` (any subset)."""
    sel = {}
    for part in filter(None, arg.split(",")):
        if "=" not in part:
            raise SystemExit(f"bad --spec entry {part!r}; use axis=value")
        axis, _, value = part.partition("=")
        if axis not in AXIS_VALUES:
            raise SystemExit(f"unknown spec axis {axis!r}; "
                             f"axes: {sorted(AXIS_VALUES)}")
        if value not in AXIS_VALUES[axis]:
            raise SystemExit(f"unknown {axis} value {value!r}; "
                             f"values: {AXIS_VALUES[axis]}")
        sel[axis] = value
    return sel


def spec_covers(axes, sel: dict) -> bool:
    """Does a suite's swept lattice include every selected axis value?"""
    if axes is None:
        return False
    return all(v in axes.get(a, ()) for a, v in sel.items())


def _cache_cmd(args) -> None:
    import importlib.util
    import json
    import pathlib

    # load cache.py by path: `import repro.core.cache` would execute the
    # package __init__ and pull in jax for a pure-admin command
    path = (pathlib.Path(__file__).resolve().parent.parent
            / "src" / "repro" / "core" / "cache.py")
    spec = importlib.util.spec_from_file_location("_repro_cache_admin", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    cache = mod.ResultCache()
    cmd = args[0] if args else "stats"
    if cmd == "stats":
        print(json.dumps(cache.stats(), indent=1))
    elif cmd == "clear":
        version = None
        rest = args[1:]
        if rest and rest[0] == "--version":
            if len(rest) < 2:
                raise SystemExit(
                    "cache clear --version needs a tag (see the `versions` "
                    "split of `cache stats`; `unversioned`/`unreadable` "
                    "match unstamped/corrupt entries)")
            version = rest[1]
            rest = rest[2:]
        if rest:
            raise SystemExit(f"unknown cache clear argument(s) {rest}")
        what = "entries" if version is None else f"{version!r} entries"
        print(f"removed {cache.clear(version=version)} {what} "
              f"from {cache.root}")
    else:
        raise SystemExit(f"unknown cache command {cmd!r}; use stats|clear")


def main() -> None:
    argv = sys.argv[1:]
    if "--list" in argv:
        _list_suites()
        return
    if argv and argv[0] == "cache":
        _cache_cmd(argv[1:])
        return
    spec_sel = None
    if "--spec" in argv:
        i = argv.index("--spec")
        if i + 1 >= len(argv):
            raise SystemExit("--spec needs an argument, e.g. "
                             "--spec queue=xqueue,barrier=tree,"
                             "balance=na_ws")
        spec_sel = parse_spec_filter(argv[i + 1])
        argv = argv[:i] + argv[i + 2:]
    if "--backend" in argv:
        i = argv.index("--backend")
        if i + 1 >= len(argv) or argv[i + 1] not in BACKEND_VALUES:
            raise SystemExit(f"--backend needs one of {BACKEND_VALUES}")
        # SimConfig.backend defaults to None, which resolves through this
        # environment variable (repro.core.backends) — setting it here
        # switches every suite in the run without touching their configs
        os.environ["REPRO_STEP_BACKEND"] = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    profile = "--profile" in argv
    if profile:
        argv.remove("--profile")
    only = set(argv)
    unknown = only - set(SUITES)
    if unknown:
        raise SystemExit(f"unknown suite(s): {sorted(unknown)}; "
                         f"available: {sorted(SUITES)} (see --list)")
    from benchmarks.common import use_compile_cache
    print(f"# compile cache: {use_compile_cache()}", flush=True)
    tracer = None
    if profile:
        # jax.profiler.trace wraps the whole selected run (viewable with
        # tensorboard / xprof); engine dispatch accounting prints at the end
        import contextlib

        import jax

        from repro.core import executors as executors_mod
        trace_dir = os.path.join("experiments", "bench", "profile")
        tracer = contextlib.ExitStack()
        tracer.enter_context(jax.profiler.trace(trace_dir))
        executors_mod.reset_engine_stats()
    failures = []
    ran = 0
    for name, info in SUITES.items():
        if (only and name not in only) or \
                (not only and name in _EXPLICIT_ONLY):
            continue
        if spec_sel is not None and not spec_covers(info["axes"], spec_sel):
            continue
        ran += 1
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        try:
            _suite_fn(name)()
            print(f"# {name} done in {time.time()-t0:.0f}s", flush=True)
        except Exception as e:  # keep the harness going; report at the end
            failures.append((name, repr(e)))
            print(f"# {name} FAILED: {e!r}", flush=True)
    if tracer is not None:
        tracer.close()
        stats = dict(executors_mod.ENGINE_STATS)
        share = (stats["sim_steps"] / stats["lane_slots"]
                 if stats["lane_slots"] else float("nan"))
        print(f"# profile: trace under {trace_dir}, the engine's host work "
              f"in its repro.* spans; {stats['dispatches']} dispatches "
              f"over {stats['chunks']} chunks, {stats['sim_steps']} "
              f"simulated steps in {stats['loop_iterations']} loop "
              f"iterations, lane-step share {share:.4f}", flush=True)
    if failures:
        print("# FAILURES:", failures)
        raise SystemExit(1)
    if ran == 0:
        # e.g. a named suite whose lattice the --spec filter excludes;
        # succeeding after running nothing would green-light a broken CI
        raise SystemExit("no suites matched the given selection/--spec "
                         "filter; see --list for suite lattices")
    print(f"# all {ran} selected benchmarks passed")


if __name__ == '__main__':
    main()
