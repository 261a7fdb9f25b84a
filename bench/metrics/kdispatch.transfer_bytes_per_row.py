"""kdispatch.transfer_bytes_per_row: bytes the device-kernel calls
copied to the device and back — the program's ``kernel.h2d_bytes`` and
``kernel.d2h_bytes`` counters (``repro.core.obs``: the padded arrays of
each call, summed) — per base-table row the window's queries scanned,
the denominator of ``data_plane.copied_bytes_per_row``.

The counters are read where they are kept, in the program.  The
harness clears them (``kdispatch.reset_device_calls``) as the window
opens and calls this reader after the window, when nothing but the
window's queries has called a device kernel since (the reference is
numpy alone), so their totals are the window's.  A program without the
counters gives None."""


def read(run):
    try:
        from repro.core import obs
    except ImportError:
        return None
    moved = obs.totals("kernel.")
    rows = sum(q["rows_scanned"] for q in run.queries)
    if "kernel.h2d_bytes" not in moved or not rows:
        return None
    return (moved["kernel.h2d_bytes"] + moved.get("kernel.d2h_bytes", 0)) \
        / rows
