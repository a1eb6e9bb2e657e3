"""Bytes one dispatch MUST move on an exact slot-addressed table (the
test's made-up second family; the shape of a real chipbench/bytes_<m>.py):
the algorithm's own need from shapes. Per decision: one directory probe
(the u64 key and its i32 slot), a read-modify-write of the slot's i64
debt and of its i64 last-refill instant, the wire columns of bytes.py.
No periodic pass: the bucket decays by arithmetic on the row it touches."""

from chipbench.bytes import WIRE_IN, WIRE_OUT

DIRECTORY_PROBE = 8 + 4
SLOT_RMW = 2 * (8 + 8)


def step_bytes(cfg: dict, batch: float, dispatches_per_s: float) -> float:
    return batch * (DIRECTORY_PROBE + SLOT_RMW + WIRE_IN + WIRE_OUT)


def step_ops(cfg: dict, batch: float) -> float:
    """splitmix64 ~16, a probe compare, the bucket's decay/compare/add."""
    return batch * (16 + 1 + 6)
