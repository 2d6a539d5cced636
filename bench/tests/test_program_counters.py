"""The program's own loop counters (``ENGINE_STATS["loop_iterations"]``
and ``["lane_slots"]``) against what ``bench.lanes`` reconstructs from the
plan and the result's steps, on one device and on the sharded executor
over 4 virtual devices."""

import os
import subprocess
import sys

import pytest

from bench.tests.helpers import REPO


def check_program_counts(config_name, traffic_name):
    from bench import lanes
    from bench.tests.test_lanes import _grid
    from repro.core import executors

    executors.reset_engine_stats()
    program, specs, res = _grid(config_name, traffic_name)
    sl = lanes.slices(program.graphs, specs, res.steps)
    stats = executors.ENGINE_STATS
    assert stats["loop_iterations"] == sum(s.iterations for s in sl) > 0
    assert (stats["sim_steps"], stats["lane_slots"]) == \
        lanes.useful_and_capacity(sl)


@pytest.mark.parametrize("traffic", ["lattice", "knobs"])
def test_one_device(traffic):
    check_program_counts("quad48-bots", traffic)


def test_four_devices_sharded():
    code = ("import sys; sys.path[:0] = [%r, %r]; "
            "from bench.tests.test_program_counters import "
            "check_program_counts; "
            "check_program_counts('quad48-bots', 'knobs'); print('ok')"
            % (REPO, os.path.join(REPO, "src")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0 and p.stdout.strip().endswith("ok"), p.stderr
