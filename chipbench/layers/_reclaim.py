"""What the reclaim readers share (PR 44): the dense backend's reclaim
pass — ``jit_dense_reclaim``, entries idle for two windows given up, run
by the launch that finds the directory over the gate's share of its
capacity (0.79875; at every launch over the line, 0.81875: since PR 48,
one line at 7/8 before) and waited for on the dispatcher's thread — as the program counts it on ``/metrics``,
recorder on or off: ``rate_limiter_directory_reclaim_passes_total``,
``…reclaimed_total`` and ``…reclaim_seconds_total`` (the ``reclaim``
span's own stamps: what the launch waited, the steps in flight ahead of
the pass included). They apply where ``--backend`` is ``dense``, the
loop is closed and the configuration's table is smaller than its key
population — sized for the ACTIVE set, so that expiry is what keeps it
from filling and a pass runs all run long (``exact-tb-ttl``; a table
that holds every key never runs one inside a run). None on a program
without the families (before PR 44) and, per pass, where no pass ran
between the scrapes (``chipbench/README-ttl.md``)."""

from chipbench.layers import _directory

LAYER = _directory.LAYER
MODULE = "jit_dense_reclaim"


def applies(cell: dict) -> bool:
    cfg = cell["config"]
    return (_directory.dense_closed(cell)
            and cfg["capacity"] < cfg["key_population"])


def passes(sources: dict):
    """Passes between the window's two scrapes; None where the program
    exports no such family (before PR 44)."""
    return _directory.moved(sources, "reclaim_passes_total")


def per_pass(sources: dict, name: str):
    """The counter's difference a pass; None without a pass."""
    n = passes(sources)
    if not n:
        return None
    moved = _directory.moved(sources, name)
    return None if moved is None else moved / n


def device_us(sources: dict):
    """Device time of one execution of the pass's module in the trace;
    None without a trace or where the capture holds no pass."""
    trace = sources.get("trace")
    for name, executions, seconds in (trace or {}).get("modules") or []:
        if name == MODULE and executions:
            return seconds / executions * 1e6
    return None
