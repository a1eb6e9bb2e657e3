"""Bytes and operations one dispatch MUST move on an exact table, a state
row a key behind a key -> slot directory (``"byte_model": "table"``; the
dense backend, ops/dense_kernels.py + ops/directory.py): the algorithm's
own need from shapes, not what today's program moves (chipbench/bytes.py
states the rule). Per decision: one directory probe (read the 8 B key,
get its 4 B slot), a read-modify-write of each int64 column of the key's
row (the token bucket's ``tokens``, ``rem``, ``last``; the sliding
window's ``curr``, ``prev``, ``win_start``; the fixed window's ``count``,
``win_start``), the wire columns of bytes.py. Nothing sized by the table:
the bucket refills by arithmetic on the row it touches, a probe that
reads a whole bucket row of 128 keys reads more than it must, and the
reclaim pass runs when the directory fills, not a dispatch — so the count
is the same whether the table holds the whole population or the keys of
the last two windows (``bytes_reclaim`` adds that pass's own bytes):
96.125 B a token-bucket decision."""

from chipbench.bytes import WIRE_IN, WIRE_OUT

DIRECTORY_PROBE = 8 + 4
COLUMN_RMW = 8 + 8
COLUMNS = {"token_bucket": 3, "sliding_window": 3, "fixed_window": 2}


def step_bytes(cfg: dict, batch: float, dispatches_per_s: float) -> float:
    row = COLUMNS[cfg["algorithm"]] * COLUMN_RMW
    return batch * (DIRECTORY_PROBE + row + WIRE_IN + WIRE_OUT)


def step_ops(cfg: dict, batch: float) -> float:
    """Integer operations a dispatch, for the record (the step is bound
    by bytes): splitmix64 ~16 an id, the home bucket's 32-bit mix ~8, a
    probe compare, the rule's refill / compare / add ~6."""
    return batch * (16 + 8 + 1 + 6)
