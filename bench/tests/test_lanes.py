"""lane_step_share and the loop's iteration count against a brute-force
count over the plan's chunks and the result's steps, on one device and on
the sharded executor over 4 virtual devices."""

import json
import os
import subprocess
import sys

from bench.tests.helpers import REPO, tiny_config


def _grid(config_name, traffic_name, seed=7):
    from bench import graphs as graphs_mod
    from bench.grid import GridSource
    from bench.harness import Program

    config = tiny_config(config_name)
    with open(os.path.join(REPO, "bench", "traffic",
                           traffic_name + ".json")) as f:
        traffic = json.load(f)
    program = Program(config, [graphs_mod.build(a, config["graph_seed"])
                               for a in config["apps"]])
    specs = program.specs(GridSource(traffic, config, seed).next())
    return program, specs, program.run(specs)


def _brute(graphs, specs, steps, n_dev):
    """Step the chunks' lanes one loop iteration at a time."""
    import jax

    from repro.core.plan import build_plan

    useful = capacity = iters = 0
    for chunk in build_plan(graphs, specs).chunks:
        p = 1
        while p < chunk.n_real:
            p *= 2
        if n_dev > 1:
            p = -(-p // n_dev) * n_dev
        lane = [int(steps[i]) for i in chunk.indices] + [0] * (
            p - chunk.n_real)
        per = p // n_dev
        assert n_dev == jax.device_count() or n_dev == 1
        for d in range(n_dev):
            mine = lane[d * per:(d + 1) * per]
            k = 0
            while any(s > k for s in mine):
                useful += sum(s > k for s in mine)
                capacity += per
                k += 1
            iters += k
    return useful, capacity, iters


def check_counts(config_name, traffic_name, n_dev):
    from bench import lanes
    from repro.core import executors

    executors.reset_engine_stats()
    program, specs, res = _grid(config_name, traffic_name)
    sl = lanes.slices(program.graphs, specs, res.steps)
    useful, capacity = lanes.useful_and_capacity(sl)
    assert (useful, capacity, sum(s.iterations for s in sl)) == _brute(
        program.graphs, specs, res.steps, n_dev)
    # the program's own count of simulated lane-steps agrees
    assert useful == executors.ENGINE_STATS["sim_steps"]
    assert 0 < useful < capacity


def test_one_device_lattice():
    check_counts("quad48-bots", "lattice", 1)


def test_four_devices_sharded():
    code = ("import sys; sys.path[:0] = [%r, %r]; "
            "from bench.tests.test_lanes import check_counts; "
            "check_counts('quad48-bots', 'knobs', 4); print('ok')"
            % (REPO, os.path.join(REPO, "src")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0 and p.stdout.strip().endswith("ok"), p.stderr
