"""step_thief_us: device microseconds per loop iteration of the step's
``thief`` phase (the thief protocol and its retry loop): the self time of
the device ops under the phase's ``jax.named_scope``, inside the loop
executables, summed over devices, over the loop iterations ``loop_iter_us``
divides by.  An op's self time is its duration less the ops it contains
(see bench.program_trace).  ``None`` where the program names no scopes."""

from bench import program_trace

program_trace.install()


def read(run):
    return program_trace.phase_us(run, "thief")
