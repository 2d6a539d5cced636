"""Step backends: who implements the phase pipeline's inner kernels.

A :class:`StepBackend` composes the phase functions of
:mod:`repro.core.phases` into the per-scheduling-point transition, choosing
the :class:`~repro.core.phases.StepOps` kernel set the phases run on:

* ``reference`` — today's pure-jnp mask arithmetic (one-hot selects, no
  scatters), the oracle every other backend is measured against.  Pinned
  bitwise to the pre-decomposition results by ``tests/golden_modes.json``.
* ``pallas``    — Pallas kernels for the hot queue traffic (the per-pair
  SPSC push / pop-scan of :mod:`repro.core.xqueue` and the one-hot counter
  bumps): compiled where the step is lowered for a TPU, interpreted where
  it is lowered for the CPU, so the same backend runs in CI.
* ``pallas_fused`` — the whole-step megakernel: the entire composed
  pipeline (adopt → spawn → dequeue → thief → victim → exec) as *one*
  Pallas launch per scheduling point (:mod:`repro.kernels.sched_step`),
  running the reference math cores inside the kernel body.  It runs
  interpreted on the CPU only: Mosaic does not lower its body, so a TPU
  compile raises the compiler's error.

Backends are **bitwise identical by contract** — same makespans, counters,
step counts on every lattice point and executor (tests/test_backends.py
asserts it per phase and end-to-end).  That contract is why the result
cache's keys deliberately exclude the backend: a cache entry written under
one backend is a valid hit under any other.

Selection threads through :class:`~repro.core.state.SimConfig.backend`
(``None`` → the ``REPRO_STEP_BACKEND`` environment variable → ``reference``;
resolved once at the public entry points so jit caches key on the concrete
name), ``sweep.run_cases(backend=…)``, and ``benchmarks/run.py --backend``.
"""

from __future__ import annotations

import abc
import os

from repro.core import dlb, phases
from repro.core.costs import CostModel
from repro.core.phases import REFERENCE_OPS, StepOps
from repro.core.state import GraphArrays, SweepCase

#: environment fallback for SimConfig.backend=None (benchmarks/run.py
#: --backend sets it process-wide before jax initializes)
ENV_VAR = "REPRO_STEP_BACKEND"


class StepBackend(abc.ABC):
    """One implementation of the step body.  Stateless; see BACKENDS."""

    name: str = "?"

    @abc.abstractmethod
    def step_ops(self) -> StepOps:
        """The kernel set the phase pipeline runs on."""

    def build_step(self, W: int, S: int, costs: CostModel, g: GraphArrays,
                   case: SweepCase, max_steps: int,
                   tables: dlb.VictimTables):
        """Compose the phase pipeline into ``step(st) -> st``.

        ``W``/``S``/``max_steps`` are static; everything
        configuration-dependent lives in the traced ``case``, and all
        spec-axis branching inside the phases is mask arithmetic — no
        Python control flow — so the returned ``step`` vmaps over a batch
        of cases.  ``tables`` is the case's
        :func:`~repro.core.dlb.victim_tables`, which the caller builds once
        per case outside its step loop.

        The composition itself is :func:`repro.core.phases.step_pipeline`
        (one definition, every backend): each phase is gated on the shared
        :func:`~repro.core.phases.run_gate` liveness predicate, so once a
        simulation finishes or stalls its step is a strict no-op.  That
        lets the batched engine drive a plain ``while any(alive)`` loop
        over vmapped steps without per-element freeze/select machinery —
        finished batch elements simply stop changing.
        """
        del W, S  # fixed by the state shapes the phases read
        ops = self.step_ops()

        def step(st):
            return phases.step_pipeline(st, g=g, case=case, tables=tables,
                                        costs=costs, ops=ops,
                                        max_steps=max_steps)

        return step


class ReferenceBackend(StepBackend):
    """Pure-jnp kernels — the bitwise oracle (golden-pinned)."""

    name = "reference"

    def step_ops(self) -> StepOps:
        return REFERENCE_OPS


class PallasBackend(StepBackend):
    """Pallas kernels for the hot queue phases (compiled for a TPU,
    interpreted on the CPU).

    The kernel set is imported lazily so merely listing backends never pulls
    in pallas machinery; see :mod:`repro.kernels.sched_queue`.
    """

    name = "pallas"

    def step_ops(self) -> StepOps:
        from repro.kernels import sched_queue
        return sched_queue.pallas_ops()


class PallasFusedBackend(StepBackend):
    """The whole-step megakernel: one Pallas launch per scheduling point.

    Instead of swapping individual queue kernels into the jnp pipeline,
    this backend lowers the *entire* composed step — adopt → spawn →
    dequeue → thief → victim → exec — into a single ``pallas_call`` (see
    :mod:`repro.kernels.sched_step`).  The kernel body runs the very same
    :func:`repro.core.phases.step_pipeline` over the reference math, so
    bitwise equality with ``reference`` holds by construction; what changes
    is the launch granularity: six phase dispatches and their intermediate
    buffer round-trips collapse into one fused kernel whose working set
    stays resident for the whole step.
    """

    name = "pallas_fused"

    def step_ops(self) -> StepOps:
        # the fused kernel runs the reference math cores *inside* the
        # megakernel; there is no per-op kernel set to expose
        return REFERENCE_OPS

    def build_step(self, W: int, S: int, costs: CostModel, g: GraphArrays,
                   case: SweepCase, max_steps: int,
                   tables: dlb.VictimTables):
        del W, S
        from repro.kernels import sched_step
        return sched_step.build_fused_step(costs, g, case, tables, max_steps)


BACKENDS = {b.name: b for b in (ReferenceBackend(), PallasBackend(),
                                PallasFusedBackend())}


def resolve_name(name: str | None) -> str:
    """Normalize ``SimConfig.backend``: ``None`` → ``$REPRO_STEP_BACKEND`` →
    ``reference``.  Resolved at the public entry points (run_schedule /
    run_cases), never inside jitted code, so compiled-function caches key on
    the concrete backend name."""
    if name is None:
        name = os.environ.get(ENV_VAR) or "reference"
    assert name in BACKENDS, \
        f"unknown step backend {name!r}; available: {sorted(BACKENDS)}"
    return name


def get_backend(name: str | None = None) -> StepBackend:
    return BACKENDS[resolve_name(name)]
