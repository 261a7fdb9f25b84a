"""ops.host_s_per_query: seconds the relational ops spent on the host
per query — the self time of the program's ``ops.*`` spans in the traced
window (``harness.spans``: each span's time less what its child spans on
its thread cover, so the ``kernel.*`` calls inside an op are left out),
over the window's queries."""

from harness import spans


def read(run):
    if run.span_self_s is None:
        return None
    return spans.seconds_per_query(run.span_self_s, "ops.",
                                   len(run.queries))
