"""Shared helpers for the benchmark harness.

Setting ``BENCH_SMOKE=1`` in the environment shrinks every instance and the
simulated machine so the whole harness runs in CI-smoke time; results are not
meaningful for paper comparisons in that mode.
"""

from __future__ import annotations

import json
import os
import time


from repro import apps as apps_mod
from repro.core import taskgraph
from repro.core.scheduler import SimConfig

OUT_DIR = "experiments/bench"

#: the checkout root (benchmarks/..)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: JAX's persistent compile cache when JAX_COMPILATION_CACHE_DIR is unset:
#: a fixed path (gitignored), so a later process finds what an earlier one
#: compiled
JAX_CACHE_DIR = os.path.join(ROOT, ".jax_cache")

#: CI smoke mode: tiny instances, tiny machine (see module docstring)
SMOKE = os.environ.get("BENCH_SMOKE", "") == "1"

#: the AppSpec scale preset every suite builds at (paper §VI scales its
#: DLB sweeps the same way; the size tables live on the registry now)
SCALE = "smoke" if SMOKE else "bench"

#: the paper's BOTS app set with its per-scale kwargs (registry-derived;
#: kept as a dict because the tuner and Fig.-suites iterate/inspect it)
APPS = {a: apps_mod.get(a).kwargs(SCALE) for a in taskgraph.BOTS_APPS}

# stack_cap 64: the BOTS-analogue DAGs never need more than ~tree-depth
# range entries per worker (overflow is detected and fails the run); the
# smaller stack cuts the per-step memory traffic of batched sweeps 8x.
SIM = (SimConfig(n_workers=16, n_zones=4, max_steps=60_000, stack_cap=64)
       if SMOKE
       else SimConfig(n_workers=32, n_zones=4, max_steps=200_000,
                      stack_cap=64))


def graph_for(app: str, **kw):
    """Build any registered app (BOTS or model-derived) at the harness
    scale; ``kw`` overrides preset knobs (e.g. ``alpha=`` for ``moe``)."""
    return apps_mod.build(app, scale=SCALE, **kw)


def emit(rows, name):
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{name}.json"), "w") as f:
        json.dump(rows, f, indent=1, default=str)


#: the shared cross-suite benchmark record: repo root at full scale, a
#: throwaway copy under experiments/bench in smoke mode (meaningless grids
#: must never overwrite the committed numbers)
BENCH_SWEEP_PATH = (
    os.path.join(OUT_DIR, "BENCH_sweep_smoke.json") if SMOKE else
    os.path.join(ROOT, "BENCH_sweep.json"))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), else
    :data:`JAX_CACHE_DIR`."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = JAX_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def merge_bench_sweep(updates: dict) -> dict:
    """Merge ``updates`` into BENCH_sweep.json without clobbering the
    sections other suites own (sweep_bench / ablation_lattice /
    step_backends all write through here).  Returns the merged record."""
    try:
        with open(BENCH_SWEEP_PATH) as f:
            record = json.load(f)
    except (OSError, ValueError):
        record = {}
    record.update(updates)
    os.makedirs(os.path.dirname(BENCH_SWEEP_PATH) or ".", exist_ok=True)
    with open(BENCH_SWEEP_PATH, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    return record


def csv_row(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.2f},{derived}")
