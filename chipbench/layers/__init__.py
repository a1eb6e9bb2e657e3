"""Per-layer metric readers, one module per metric.

A reader is a module with ``META`` (name, unit, better, layer, moves,
source, applies) and ``read(sources)``. ``applies`` is a predicate over
the cell — ``{"name", "chips", "config": {...}, "traffic": {...}}`` —
and never a list of cell names. ``read`` returns a number, or None when
there is nothing to read (the harness then leaves the metric out).

``sources``: ``cell``; ``loadgen`` (the generator's JSON);
``metrics_start`` / ``metrics_end`` (parsed /metrics scrapes at the
window's ends, chipbench/promtext.py); ``trace`` (chipbench/
trace_reduce.py's output, or None); ``server_log`` (the server's
stderr); ``peaks`` (the chipbench/peaks.json row of the device kind).
"""

from __future__ import annotations

import importlib
import pkgutil


def load() -> list:
    """Every reader module of this directory, by name."""
    names = sorted(m.name for m in pkgutil.iter_modules(__path__)
                   if not m.name.startswith("_"))
    return [importlib.import_module(f"{__name__}.{n}") for n in names]


def closed_loop(cell: dict) -> bool:
    return cell["traffic"]["loop"] == "closed"


def open_loop(cell: dict) -> bool:
    return cell["traffic"]["loop"] == "open"


def stage_us(sources: dict, stage: str):
    """Mean microseconds of one flight-recorder stage per recorded span
    between the two scrapes (``rate_limiter_stage_seconds``)."""
    from chipbench import promtext

    mean = promtext.mean_of(sources["metrics_start"], sources["metrics_end"],
                            "rate_limiter_stage_seconds", stage=stage)
    return None if mean is None else mean * 1e6


def twin(closed_module, moves: str) -> dict:
    """META of the open-loop twin of a closed-loop reader: the same
    number, reported where ``moves`` is."""
    return {**closed_module.META, "name": closed_module.META["name"] + "_open",
            "moves": moves, "applies": open_loop}
