"""The row check catches a broken timed path: each fault below is planted
in the program underneath a full run of the harness (the accelerator check
skipped, tiny configurations on the host CPU), and ``correct`` must come
out false."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench.tests.helpers import REPO, make_root

ARGS = ["--seed", "2147483659", "--seconds", "0.5", "--trace", "0"]


def _state_unchanged(mp):
    """The device loop hands back the state it was given."""
    from repro.core import executors
    mp.setattr(executors, "_run_batch",
               lambda cfg, gq_cap, gb, cb, st0: st0)


def _wrap_collect(mp, cls, change):
    orig = cls.collect

    def collect(self, pending):
        raw = orig(self, pending)
        return type(raw)(*(change(np.array(a), pending) for a in raw))

    mp.setattr(cls, "collect", collect)


def _half_batch(mp):
    """Half of each chunk's lanes left out, their rows copied from the
    other half."""
    from repro.core.executors import VmapExecutor

    def change(a, pending):
        n = a.shape[0]
        a[n - n // 2:] = a[:n // 2]
        return a

    _wrap_collect(mp, VmapExecutor, change)


def _answer_altered(mp):
    """Each worker's clock one ns late where the chunk's rows are made."""
    from repro.core.executors import VmapExecutor

    def change(a, pending):
        return a + 1 if a.ndim == 2 and a.dtype != bool else a

    _wrap_collect(mp, VmapExecutor, change)


def _no_exchange(mp):
    """Only the first device's lanes come back; the others' rows are
    never fetched."""
    import jax

    from repro.core.executors import ShardedExecutor

    def change(a, pending):
        per = pending[0].step_i.shape[0] // jax.device_count()
        a[per:] = 0
        return a

    _wrap_collect(mp, ShardedExecutor, change)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


def run_with_fault(tmp, fault, cell, chips=None):
    root = make_root(tmp, cells=[cell])
    if chips is not None:       # the same cell, on ``chips`` devices
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        bench["workloads"][0]["chips"] = chips
        with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f)
    with pytest.MonkeyPatch.context() as mp:
        fault(mp)
        rc = harness.main(["--workload", cell, *ARGS], root=root,
                          require_accelerator=False)
    return rc


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(fault, tmp_path, capsys):
    assert run_with_fault(tmp_path, FAULTS[fault], "quad48-lattice") == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is False
    assert out["check"]["mismatched_rows"]["value"] > 0


def test_sound_run_is_correct(tmp_path, capsys):
    assert run_with_fault(tmp_path, lambda mp: None, "quad48-lattice") == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True


def test_missing_exchange_is_caught(tmp_path):
    code = ("import sys, json; sys.path[:0] = [%r, %r]; "
            "from bench.tests import test_faults as t; "
            "t.run_with_fault(%r, t._no_exchange, 'quad48-knobs', 4)"
            % (REPO, os.path.join(REPO, "src"), str(tmp_path)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"] is False
