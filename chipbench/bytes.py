"""Bytes and operations one dispatch of the device step MUST move: the
algorithm's own need, from shapes, not what today's program moves. A
kernel's share of its roofline divides this by the time the trace shows.

Windowed count-min sketch (ops/sketch_kernels.py state: ``cur``,
``totals`` int32[d, w], a ring ``slabs`` int32[S, d, w]). Per decision
and row: read the window total and the boundary sub-window's cell (the
sliding estimate), read-modify-write ``cur`` and ``totals``. A denial
does the same reads and compares; its write is masked, so the cells are
touched all the same. Once per sub-window the ring rotates: ``cur`` is
flushed into a slot, the evicted slot leaves ``totals``, ``cur`` is
zeroed — six passes over d x w cells, shared by every dispatch of that
sub-window.

Sketched token bucket (ops/bucket_kernels.py: ``debt``, ``acc``
int64[d, w]). Per decision and row: read ``debt``, read-modify-write
``debt`` and ``acc``. The decay is one scalar per step.

Both: ids in (u64 id + u32 n), the packed reply out (1 bit allowed +
i64 remaining + f64 retry + f64 reset).
"""

from __future__ import annotations

CELL = {"tpu_sketch": 4, "sliding_window": 4, "token_bucket": 8}
WIRE_IN = 8 + 4
WIRE_OUT = 8 + 8 + 8 + 1 / 8


def decision_bytes(algorithm: str, depth: int) -> float:
    """Bytes per decision: table cells touched plus wire columns."""
    cell = CELL[algorithm]
    if algorithm == "token_bucket":
        cells = depth * (cell + 2 * cell + 2 * cell)   # read, RMW debt, RMW acc
    else:
        cells = depth * (2 * cell + 2 * cell + 2 * cell)  # 2 reads, 2 RMW
    return cells + WIRE_IN + WIRE_OUT


def rotation_bytes(algorithm: str, depth: int, width: int) -> int:
    """Bytes one sub-window rotation must move (0 for the bucket)."""
    if algorithm == "token_bucket":
        return 0
    return 6 * depth * width * CELL[algorithm]


def step_bytes(cfg: dict, batch: float, dispatches_per_s: float) -> float:
    """Bytes one dispatch of ``batch`` decisions must move, with its
    share of the rotations (``sub_windows / window_s`` per second)."""
    algo = cfg["algorithm"]
    per_dispatch = batch * decision_bytes(algo, cfg["depth"])
    if dispatches_per_s > 0:
        rotations_per_s = cfg.get("sub_windows", 0) / cfg["window_s"]
        per_dispatch += (rotation_bytes(algo, cfg["depth"], cfg["width"])
                         * rotations_per_s / dispatches_per_s)
    return per_dispatch


def step_ops(cfg: dict, batch: float) -> float:
    """Integer operations per dispatch, for the record (the step is bound
    by bytes): splitmix64 and the (h1, h2) split ~16 per id, then per row
    a column (3), a compare/min (2) and two adds (2)."""
    return batch * (16 + 7 * cfg["depth"])
