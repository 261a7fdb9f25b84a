"""data_plane.copied_bytes_per_row: real memcpys of the data plane
(``BufferStore.stats.bytes_copied``, summed over the window's queries)
per base-table row those queries scanned."""


def read(run):
    rows = sum(q["rows_scanned"] for q in run.queries)
    if not rows:
        return None
    return sum(q["bytes_copied"] for q in run.queries) / rows
