"""compile_s: seconds JAX spent compiling or loading compiled programs from
its persistent cache during set-up, from its ``backend_compile_duration``
monitoring events."""


def read(run):
    return run.compile_setup_s
