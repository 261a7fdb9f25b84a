"""kdispatch.call_s_per_query: seconds inside the program's device-kernel
calls per query — the self time of every ``kernel.*`` span in the traced
window (``harness.spans``: a call's copy in, run, copy out and the host
padding and slicing around them), over the window's queries."""

from harness import spans


def read(run):
    if run.span_self_s is None:
        return None
    return spans.seconds_per_query(run.span_self_s, "kernel.",
                                   len(run.queries))
