"""submit_ms: mean milliseconds of the program's ``repro.submit`` span, one
per chunk (stacking, state init and the run's dispatch), over the chunks
submitted in the traced window.  ``None`` where the program has no
spans."""

from bench import program_trace

program_trace.install()


def read(run):
    spans = program_trace.spans_in_window(run.trace, "repro.submit")
    if not spans:
        return None
    return sum(d for _, _, d, _ in spans) / len(spans) / 1e6
