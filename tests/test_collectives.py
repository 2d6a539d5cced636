"""Hierarchical (tree) collectives: correctness vs flat psum, and inter-pod
byte reduction, via an 8-device subprocess."""

import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np
from functools import partial
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.runtime.collectives import tree_allreduce, flat_psum_grads, hierarchical_psum_grads
from repro.launch import hlo_analysis as ha

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
x = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 2, 64))

def flat(v):
    return jax.lax.psum(v, ("pod", "data"))

def tree(v):
    return tree_allreduce(v, intra_axes=("data",), inter_axis="pod")

spec = P("pod", "data", "model", None)
run_flat = jax.jit(jax.shard_map(flat, mesh=mesh, in_specs=spec, out_specs=spec))
run_tree = jax.jit(jax.shard_map(tree, mesh=mesh, in_specs=spec, out_specs=spec))
a = run_flat(x); b = run_tree(x)
np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

# non-divisible fallback path
y = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 2, 3))
spec3 = P("pod", "data", "model", None)
a = jax.jit(jax.shard_map(flat, mesh=mesh, in_specs=spec3, out_specs=spec3))(y)
b = jax.jit(jax.shard_map(tree, mesh=mesh, in_specs=spec3, out_specs=spec3))(y)
np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

print("PASS")
"""


@pytest.mark.slow
def test_tree_allreduce_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"})
    assert "PASS" in r.stdout, r.stdout + r.stderr
