"""Bytes that the relational operators need, from the reference's
cardinalities, and the roofline share those bytes give.

The relational kernels move 64-bit integers, which the TPU emulates;
no public peak exists for emulated 64-bit arithmetic, so the least time
here is bandwidth alone: bytes over the chip's HBM peak.  The bytes are
the least that any implementation of the operator moves, whatever its
algorithm, so a share can only exceed 100% where the device time leaves
out part of the work.

* A hash join reads each side's key column once and writes one (left,
  right) pair of int64 row indices per match, and a left join one
  (left, -1) pair per left row that matches nothing:
  ``(left_rows + right_rows) * key_bytes + (matches + misses) * 16``.
* A group-by aggregate reads each row's value and its int64 group
  position and writes one int64 accumulator per group:
  ``rows * (value_bytes + 8) + groups * 8``.
* A ``like`` (or ``not like``) reads the column's text and its int64
  offsets (``harness.program``'s utf8 layout: ``rows + 1`` of them)
  and writes one bool a row:
  ``value_bytes + (rows + 1) * 8 + rows``.
"""

from __future__ import annotations

from typing import Iterable, Optional

#: the jitted functions (``kernels/relational.py``) whose device time
#: each share divides by
JOIN_PROGRAMS = ("_hash_core", "_combine_core", "_hash_keys_core",
                 "_gather_core")
REDUCE_PROGRAMS = ("_segreduce_core",)


def join_bytes(op: dict) -> int:
    return (op["left_rows"] + op["right_rows"]) * op["key_bytes"] \
        + (op["matches"] + op.get("misses", 0)) * 16


def group_by_bytes(op: dict) -> int:
    return sum(op["rows"] * (vb + 8) + op["groups"] * 8
               for vb in op["value_bytes"])


def like_bytes(op: dict) -> int:
    return op["value_bytes"] + (op["rows"] + 1) * 8 + op["rows"]


def share(needed_bytes: int, device_s: float, hbm_bytes_per_s: float
          ) -> Optional[float]:
    """Least time over device time, in percent; None without device
    time (the kernels did not run in the window)."""
    if device_s <= 0:
        return None
    return 100.0 * needed_bytes / hbm_bytes_per_s / device_s


def program_seconds(program_s: dict, programs: Iterable[str]) -> float:
    return sum(program_s.get(p, 0.0) for p in programs)
