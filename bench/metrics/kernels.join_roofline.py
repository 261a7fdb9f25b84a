"""kernels.join_roofline: the join kernels' share of the HBM roofline.
Least time is the bytes every join of the window's queries needs
(``harness.roofline.join_bytes``, from the reference's cardinalities)
over the chip's HBM peak; it is divided by the device time of the join
programs (``_hash_core``, ``_combine_core``, ``_hash_keys_core``,
``_gather_core``) in the trace."""

from harness import roofline


def read(run):
    if run.trace is None:
        return None
    needed = sum(roofline.join_bytes(op) for q in run.queries
                 for op in q["ops"] if op["op"] == "join")
    return roofline.share(
        needed, roofline.program_seconds(run.trace["program_s"],
                                         roofline.JOIN_PROGRAMS),
        run.peaks["hbm_bytes_per_s"])
