"""Host CPU time per engine fold call (``accumulate`` in the batch cells,
``absorb`` in the stream cell): the mean over the window's calls of the
program's own span CPU time (``time.thread_time_ns``), the value each call
observed into ``span_cpu_seconds{stage="accumulate"|"absorb"}``."""
from bench import program_spans


def read(ctx):
    spans = program_spans.fold_spans(ctx.trace)
    if not spans:
        return None
    return 1e-3 * sum(s[3] for s in spans) / len(spans)
