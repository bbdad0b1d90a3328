"""The plain reference for DPOR's racing analysis: from one lane's
parent-tracked trace, the backtrack prescriptions its races give, written
from the definition and importing nothing of the program.

A record is a row of ints: ``[kind, src, dst, msg..., parent, prev]``.
``parent`` is the trace position of the record whose handling created this
record's message, ``prev`` the position of the receiver's previous record
(-1 where there is none). Happens-before is the closure of those two
edges. Deliveries ``i < j`` race when they have the same receiver, ``j``'s
message was already created when ``i`` ran (``parent(j) < i``), and no
record ``k`` lies between them in happens-before (``i`` before ``k``
before ``j``). The prescription of a race is the lane's deliveries before
``i``, then ``j``'s record: the schedule that runs ``j`` in ``i``'s place.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

KIND_DELIVERY = 1     # device/core.py: REC_DELIVERY
KIND_TIMER = 2        # REC_TIMER
KIND_EXT_BASE = 10    # REC_EXT_BASE + op

Row = Tuple[int, ...]


def rows_of(records, trace_len: int, rec_width: int) -> List[Row]:
    return [
        tuple(int(x) for x in records[p][:rec_width]) for p in range(int(trace_len))
    ]


def is_delivery(row: Row) -> bool:
    return row[0] in (KIND_DELIVERY, KIND_TIMER)


def racing_pairs(rows: Sequence[Row]) -> List[Tuple[int, int]]:
    n = len(rows)
    past = [set() for _ in range(n)]          # everything that happens before p
    for p, row in enumerate(rows):
        for q in (row[-2], row[-1]):
            if 0 <= q < p:
                past[p].add(q)
                past[p] |= past[q]
    deliveries = [p for p in range(n) if is_delivery(rows[p])]
    pairs = []
    for j in deliveries:
        for i in deliveries:
            if i >= j or rows[i][2] != rows[j][2] or rows[j][-2] >= i:
                continue
            if any(i in past[k] for k in past[j]):
                continue                      # something lies between them
            pairs.append((i, j))
    return pairs


def prescriptions(rows: Sequence[Row]) -> List[Tuple[Row, ...]]:
    """Every race's prescription, in the scan's order (by ``j``, then ``i``)."""
    out = []
    for i, j in racing_pairs(rows):
        prefix = [rows[p] for p in range(i) if is_delivery(rows[p])]
        out.append(tuple(prefix) + (rows[j],))
    return out
