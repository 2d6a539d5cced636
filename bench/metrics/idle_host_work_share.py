"""idle_host_work_share: the share of the traced window, mean over
devices, in which the device is idle while the host's innermost span is
one of the program's own (``repro.*``) other than ``repro.wait`` and
``repro.run_cases`` itself: the part of ``device_idle_share`` that the
program's host work causes.  ``None`` where the program has no spans."""

from bench import program_trace

program_trace.install()


def read(run):
    return program_trace.idle_host_work_share(run.trace)
