"""Bring-up check: the sweep engine's main path on a TPU, checked bitwise.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # a four-chip host: sharded vs vmap

One chip, two phases, both through ``run_cases`` / ``run_grid``:

* golden: the cases of ``tests/golden_modes.json`` on the TPU (vmap
  executor), once per step backend that compiles for the TPU; makespans,
  step counts and every counter must equal the golden record.
* real size: the 12-point RuntimeSpec lattice on ``quad_socket_48`` at its
  48 workers, BOTS ``fib``/``sort``/``uts`` at bench scale, 2 seeds; every
  case must complete, and the rows must equal the same grid run in this
  process on the host's CPU backend.

``--four-chips`` runs only the real-size grid, with the ``sharded``
executor over every chip and then ``vmap`` on chip 0, and requires equal
rows.

JAX's compile cache is kept where ``JAX_COMPILATION_CACHE_DIR`` says, else
in the checkout's ``.jax_cache``.  Every phase runs in this one process: a
child process could not reach a chip this process holds.  Timings printed
on the way are bring-up readings, not benchmark metrics.  The last line of
a passing run is the JSON summary; any mismatch or error exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: real-size grid: quad_socket_48 at its real core count
GRID_APPS = ("fib", "sort", "uts")
GRID_SEEDS = (0, 1)
GRID_TOPOLOGY = "quad_socket_48"
GRID_WORKERS = 48

#: step backends whose kernels compile for the TPU; the golden phase runs
#: each of them
CHIP_BACKENDS = ("reference", "pallas")
#: step backends that do not compile for the TPU, and the compiler's reason
NOT_RUN = {"pallas_fused": "Mosaic has no lowering for scatter-add in its "
                           "step pipeline; ROADMAP Speed 2"}


class Mismatch(AssertionError):
    """A result differs from what it is checked against."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise Mismatch(what)


class CompileClock:
    """Seconds JAX spends compiling (or loading from its persistent cache),
    read from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def timed(self, fn):
        """Run ``fn`` twice; returns its result and (cold, compile, warm)
        seconds: the first call, the compile/cache-load time inside it, and
        the second call."""
        c0, t0 = self.seconds, time.perf_counter()
        out = fn()
        cold = time.perf_counter() - t0
        compile_s = self.seconds - c0
        t1 = time.perf_counter()
        again = fn()
        warm = time.perf_counter() - t1
        return out, again, (cold, compile_s, warm)


def _report(label: str, kind: str, secs) -> None:
    cold, compile_s, warm = secs
    print(f"[{kind}] {label}: first call {cold:.2f} s "
          f"(compile or cache load {compile_s:.2f} s), "
          f"warm {warm:.2f} s", flush=True)


def _rows_equal(a, b, what: str) -> None:
    """Bitwise equality of two SweepResults, row by row."""
    import numpy as np
    _check(len(a.specs) == len(b.specs), f"{what}: row counts differ")
    for name in ("time_ns", "steps", "completed", "p50_ns", "p90_ns",
                 "p99_ns", "throughput"):
        _check(np.array_equal(getattr(a, name), getattr(b, name)),
               f"{what}: {name} differs")
    _check(a.counters.keys() == b.counters.keys(), f"{what}: counter names")
    for name in a.counters:
        _check(np.array_equal(a.counters[name], b.counters[name]),
               f"{what}: counter {name} differs")
    for i in range(len(a.specs)):
        _check(a.row(i) == b.row(i), f"{what}: row {i} differs")


def golden_phase(backend: str, kind: str, clock: CompileClock) -> None:
    """The golden cases on the default device through the vmap executor."""
    from repro.core import taskgraph
    from repro.core.scheduler import CTR_NAMES, SimConfig
    from repro.core.spec import RuntimeSpec
    from repro.core.sweep import CaseSpec, run_cases

    with open(os.path.join(ROOT, "tests", "golden_modes.json")) as f:
        golden = json.load(f)
    cfg = SimConfig(**golden["cfg"])
    graphs = {name: taskgraph.build(builder, **kw)
              for name, (builder, kw) in golden["graphs"].items()}
    names = list(graphs)
    specs = [CaseSpec(spec=RuntimeSpec.from_mode(c["mode"]),
                      n_workers=cfg.n_workers, n_zones=cfg.n_zones,
                      graph=names.index(c["graph"]), **golden["knobs"])
             for c in golden["cases"]]

    def run():
        return run_cases(list(graphs.values()), specs, cfg=cfg, cache=None,
                         strategy="vmap", backend=backend)

    res, again, secs = clock.timed(run)
    _report(f"golden phase, {backend} backend, {len(specs)} cases", kind,
            secs)
    for r in (res, again):
        _check(bool(r.completed.all()), f"golden/{backend}: incomplete case")
        for i, c in enumerate(golden["cases"]):
            label = f"golden/{backend}/{c['graph']}/{c['mode']}"
            _check(int(r.time_ns[i]) == c["time_ns"], f"{label}: time_ns")
            _check(int(r.steps[i]) == c["steps"], f"{label}: steps")
            for name in CTR_NAMES:
                want = c["counters"].get(name, 0)
                _check(int(r.counters[name][i]) == want, f"{label}: {name}")
    print(f"golden phase, {backend} backend: {len(specs)} cases match "
          "tests/golden_modes.json bitwise", flush=True)


def real_size_grid(strategy: str):
    """The real-size lattice grid as a thunk over ``run_grid``."""
    from repro import apps
    from repro.core import spec
    from repro.core.scheduler import SimConfig
    from repro.core.sweep import run_grid

    graphs = [apps.build(a, scale="bench") for a in GRID_APPS]
    cfg = SimConfig(max_steps=200_000, stack_cap=64)

    def run():
        return run_grid(graphs, queues=spec.QUEUES, barriers=spec.BARRIERS,
                        balancers=spec.BALANCERS, topologies=(GRID_TOPOLOGY,),
                        n_workers=(GRID_WORKERS,), seeds=GRID_SEEDS, cfg=cfg,
                        cache=None, strategy=strategy, backend="reference")

    return run


def _check_complete(res, what: str) -> None:
    _check(bool(res.completed.all()),
           f"{what}: {int((~res.completed).sum())} cases incomplete or "
           "overflowed")


def real_size_phase(kind: str, clock: CompileClock) -> None:
    import jax

    run = real_size_grid("vmap")
    tpu, again, secs = clock.timed(run)
    n = len(tpu.specs)
    _report(f"real-size phase, {n} cases on {GRID_TOPOLOGY} at "
            f"{GRID_WORKERS} workers", kind, secs)
    _check_complete(tpu, "real-size/tpu")
    _rows_equal(tpu, again, "real-size: tpu run vs its repeat")
    cpu_dev = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    with jax.default_device(cpu_dev):
        cpu = run()
    print(f"[cpu] real-size phase, same grid on the host CPU backend: "
          f"{time.perf_counter() - t0:.2f} s", flush=True)
    _rows_equal(tpu, cpu, "real-size: tpu vs cpu")
    print(f"real-size phase: {n} cases complete; rows equal the CPU "
          f"backend's bitwise; steps {int(tpu.steps.min())}.."
          f"{int(tpu.steps.max())}", flush=True)


def four_chip_phase(kind: str, clock: CompileClock) -> None:
    import jax

    from repro import apps
    from repro.core.executors import EXECUTORS
    from repro.core.plan import build_plan

    n_dev = jax.device_count()
    _check(n_dev == 4, f"--four-chips needs 4 devices, found {n_dev}")
    sharded, _, secs = clock.timed(real_size_grid("sharded"))
    _report(f"real-size grid, sharded over {n_dev} chips", kind, secs)
    vmap, _, secs = clock.timed(real_size_grid("vmap"))
    _report("real-size grid, vmap on chip 0", kind, secs)
    _check_complete(sharded, "four-chips/sharded")
    _rows_equal(sharded, vmap, "four-chips: sharded vs vmap")
    graphs = [apps.build(a, scale="bench") for a in GRID_APPS]
    lanes = {EXECUTORS["sharded"].padded_size(c) // n_dev
             for c in build_plan(graphs, sharded.specs).chunks}
    print(f"four chips: {len(sharded.specs)} cases, sharded rows equal "
          f"vmap rows bitwise; lanes per device per chunk: "
          f"{sorted(lanes)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-vmap real-size grid on a "
                         "four-chip host")
    args = ap.parse_args(argv)

    # the CPU comparison needs the host backend next to the TPU
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax

    devices = jax.devices()
    print("devices:", devices, flush=True)
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    kind = devices[0].device_kind

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from benchmarks.common import use_compile_cache
    print("compile cache:", use_compile_cache(), flush=True)
    clock = CompileClock()

    if args.four_chips:
        four_chip_phase(kind, clock)
    else:
        print(f"step backends run: {', '.join(CHIP_BACKENDS)}; not run: "
              + "; ".join(f"{b} ({why})" for b, why in NOT_RUN.items()),
              flush=True)
        for backend in CHIP_BACKENDS:
            golden_phase(backend, kind, clock)
        real_size_phase(kind, clock)
    print(f"[{kind}] compile or cache load, whole run: {clock.seconds:.2f} s "
          f"({clock.cache_hits} persistent-cache hits)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
