"""loaders.load_s_per_query: seconds the loader nodes (zarquet read and
decode, DeCache insert, SIPC write) took per query, from the executor's
per-node ``exec_latency`` summed over each query's loader nodes."""


def read(run):
    if not run.queries:
        return None
    return sum(q["load_s"] for q in run.queries) / len(run.queries)
