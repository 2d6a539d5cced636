"""Compiles of the sweep engine for a described TPU v5e, with no chip attached.

The TPU compiler is installed on CPU hosts and compiles for a topology that
is described, not attached.  These tests keep the engine's main program
compiling for the chip: the batched run on one chip, the same body under
``shard_map`` over a 2x2 mesh, and every Pallas step backend, whose kernels
must lower through Mosaic (``tpu_custom_call``) or raise the TPU compiler's
error, never fall back to the interpreter.  Nothing here runs: results and
times need the chip (``chip_smoke.py``).

The topology is described inside a fixture, so collecting this file touches
no TPU library.
"""

import functools
import math
import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro import apps
from repro.core import executors
from repro.core.plan import CaseSpec, build_plan
from repro.core.scheduler import SimConfig, graph_arrays
from repro.core.spec import RuntimeSpec

#: the compiled chunk: bench-scale fib on quad_socket_48, 32 workers x 8
#: lanes (one compiled shape serves every lattice point)
W, LANES = 32, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _chunk(backend: str):
    """Static config and abstract (graph, case, state) batch of one chunk."""
    g = apps.build("fib", scale="bench")
    cfg = SimConfig(n_workers=W, max_steps=200_000, stack_cap=64,
                    backend=backend)
    spec = RuntimeSpec(queue="xqueue", barrier="tree", balance="na_ws")
    specs = [CaseSpec(spec=spec, n_workers=W, seed=s,
                      topology="quad_socket_48") for s in range(LANES)]
    plan = build_plan([g], specs)
    ctx = executors.ExecContext(cfg=cfg, gq_cap=plan.gq_cap, graphs=[g],
                                garr=[graph_arrays(g, plan.t_pad, xp=np)])
    gb, cb = executors._stack_chunk(ctx, specs, LANES)
    st = jax.eval_shape(
        functools.partial(executors._init_body, cfg, plan.gq_cap), gb, cb)
    return cfg, plan.gq_cap, (gb, cb, st)


def _placed(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile_one_chip(topo, backend: str):
    cfg, gq_cap, args = _chunk(backend)
    one_chip = SingleDeviceSharding(topo.devices[0])
    return executors._run_batch.lower(
        cfg, gq_cap, *_placed(args, one_chip)).compile()


@pytest.fixture(scope="module")
def reference_one_chip(topo, no_compile_cache):
    return _compile_one_chip(topo, "reference")


def _computations(text: str) -> dict:
    """The HLO text's computations: name -> instruction lines."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head and line.rstrip().endswith("{"):
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _while_body_lines(text: str) -> list:
    """Instruction lines of every ``while`` body and of every computation
    those bodies call (fusions, nested loops, reductions), transitively."""
    comps = _computations(text)
    callee = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)")
    todo = [m for lines in comps.values() for line in lines
            if " while(" in line
            for m in re.findall(r"body=%([\w.\-]+)", line)]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        todo += [c for line in comps[name] for c in callee.findall(line)]
    return [line for name in seen for line in comps[name]]


def test_victim_tables_are_not_rebuilt_inside_the_loop(reference_one_chip):
    """The thief's victim-weight tables are built once per case, before the
    device loop: no instruction inside a ``while`` body that comes from the
    thief phase is a gather with one element per (lane, thief, candidate)."""
    body = _while_body_lines(reference_one_chip.as_text())
    thief = [line for line in body if "vmap(thief)" in line]
    assert thief, "no thief-phase instruction found in the loop body"
    table_size = LANES * W * W
    rebuilt = []
    for line in thief:
        out = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]*)\]", line)
        op = re.search(r'op_name="([^"]*)"', line)
        if out is None or op is None or op.group(1).split("/")[-1] != "gather":
            continue
        dims = [int(d) for d in out.group(1).split(",") if d]
        if math.prod(dims) == table_size:
            rebuilt.append(line[:160])
    assert not rebuilt, rebuilt


def test_reference_run_batch_compiles_for_one_chip(reference_one_chip):
    compiled = reference_one_chip
    mem = compiled.memory_analysis()
    # the donated state aliases into the loop carry
    assert mem.alias_size_in_bytes > 0
    assert mem.argument_size_in_bytes < 16 * 2**30


def test_batched_body_compiles_under_shard_map_on_four_chips(
        topo, no_compile_cache):
    """The sharded executor's program: one while loop per chip over its
    slice of the lanes, with no collectives between chips."""
    cfg, gq_cap, args = _chunk("reference")
    mesh = Mesh(np.asarray(topo.devices[:4]), ("b",))
    body = jax.shard_map(functools.partial(executors._batch_body, cfg, gq_cap),
                         mesh=mesh, in_specs=(P("b"),) * 3, out_specs=P("b"),
                         check_vma=False)
    compiled = jax.jit(body).lower(
        *_placed(args, NamedSharding(mesh, P("b")))).compile()
    text = compiled.as_text()
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective


def test_pallas_backend_compiles_mosaic_kernels(topo, no_compile_cache):
    compiled = _compile_one_chip(topo, "pallas")
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_fused_backend_raises_the_tpu_compiler_error(
        topo, no_compile_cache):
    """The fused step pipeline does not lower through Mosaic (ROADMAP Speed
    2).  A TPU lowering raises Mosaic's own error; it does not fall back to
    the interpreter."""
    with pytest.raises(NotImplementedError, match="scatter-add"):
        _compile_one_chip(topo, "pallas_fused")
