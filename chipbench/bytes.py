"""Bytes and operations one dispatch of the device step MUST move: the
algorithm's own need, from shapes, not what today's program moves. A
kernel's share of its roofline divides this by the time the trace shows.

A configuration names its byte model (``"byte_model"`` in its file; none
named means ``"sketch"``, the arithmetic of this module). Any other name
``m`` is a module ``chipbench/bytes_<m>.py`` with the same two
functions, ``step_bytes(cfg, batch, dispatches_per_s)`` and
``step_ops(cfg, batch)``; ``step_bytes`` and ``step_ops`` below hand the
call over, so a reader never indexes a configuration's geometry itself.
The rule a new model must keep is the one above: count what the
ALGORITHM has to read and write for ``batch`` decisions on state of the
configuration's shapes (its cells, its wire columns, its periodic passes
shared over the dispatches of their period) — never what the program of
the day happens to move, or a later PR that moves fewer bytes would read
as a worse share.

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

import importlib
import re

#: The contract's pattern for a name: a metric, a cell, a configuration,
#: a traffic mix, a byte model.
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
DEFAULT_MODEL = "sketch"

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


class NoByteModel(ValueError):
    """The configuration names no byte model this checkout has."""


def model_of(cfg: dict):
    """The module that counts for ``cfg``: None for the sketch (this
    module's own functions), else ``chipbench.bytes_<byte_model>``."""
    name = cfg.get("byte_model", DEFAULT_MODEL)
    if name == DEFAULT_MODEL:
        lacking = [k for k in ("depth", "width") if k not in cfg]
        if lacking:
            raise NoByteModel(
                f"the configuration names no byte_model, so it is held to "
                f"the {DEFAULT_MODEL!r} model, which needs {lacking}: name "
                f"a byte_model (chipbench/bytes_<model>.py) for a state "
                f"that is not a count-min sketch")
        return None
    if not isinstance(name, str) or not NAME.match(name):
        raise NoByteModel(f"byte_model {name!r} is not a name")
    try:
        return importlib.import_module(f"chipbench.bytes_{name}")
    except ModuleNotFoundError as exc:
        raise NoByteModel(f"byte_model {name!r}: no module "
                          f"chipbench/bytes_{name}.py") from exc


def step_bytes(cfg: dict, batch: float, dispatches_per_s: float) -> float:
    """Bytes one dispatch of ``batch`` decisions must move, with its
    share of the rotations (``sub_windows / window_s`` per second)."""
    model = model_of(cfg)
    if model is not None:
        return model.step_bytes(cfg, batch, dispatches_per_s)
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
    model = model_of(cfg)
    if model is not None:
        return model.step_ops(cfg, batch)
    return batch * (16 + 7 * cfg["depth"])
