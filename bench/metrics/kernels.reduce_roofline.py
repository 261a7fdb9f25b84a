"""kernels.reduce_roofline: the segment reducers' share of the HBM
roofline.  Least time is the bytes every group-by aggregate of the
window's queries needs (``harness.roofline.group_by_bytes``: rows in,
groups out, from the reference's cardinalities) over the chip's HBM
peak; it is divided by the device time of ``_segreduce_core``."""

from harness import roofline


def read(run):
    if run.trace is None:
        return None
    needed = sum(roofline.group_by_bytes(op) for q in run.queries
                 for op in q["ops"] if op["op"] == "group_by")
    return roofline.share(
        needed, roofline.program_seconds(run.trace["program_s"],
                                         roofline.REDUCE_PROGRAMS),
        run.peaks["hbm_bytes_per_s"])
