"""Device time a dispatch spends carrying the state across the step
program's boundary: ops of the step's program that touch whole state
leaves and no batch row. From the device planes, never a host timer.

How the op set is chosen: the trace's ``custom-call`` op group
(``trace_reduce.op_group``: the HLO name with its digits stripped),
divided by the executions of the step module. On a chip without 64-bit
vectors XLA splits every 64-bit array that enters a program into 32-bit
halves and recombines every one that leaves it, and the v5e's trace
names those passes ``custom-call``. Until PR 43 the dense state's
leaves were int64 (``cols``, ``dir_keys``), so the group was a pass over
the WHOLE table a dispatch — 246 us of a 1,043 us step at 2^21 entries
(PR 33), ~31 ms at 2^26 (ledger, PR 41). Since PR 43 the state lives on
the chip as the 32-bit words the chip computes on (``cols uint32[2K,
C+1]``, ``dir_lo`` / ``dir_hi``): what is left in the group is the
staged batch's and the override table's own uint64 words, ~4 us.

At a few us the group may fall out of the ten groups
``trace_reduce.py`` keeps: a trace that ran the step and lists no
``custom-call`` group therefore reads 0.0 (the truth is under the
tenth-largest group's time, which ``breakdown.device_ops`` shows), not
None. None only without a trace or without a step in it."""

from chipbench.layers import _memory

GROUP = "custom-call"

META = {"name": "boundary_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": "device step", "moves": "decisions_per_s",
        "source": "device_trace", "applies": _memory.applies}


def read(sources: dict):
    trace = sources.get("trace")
    if not trace or not trace.get("step") or not trace["step"]["executions"]:
        return None
    seconds = sum(s for name, s in trace.get("device_ops") or []
                  if name == GROUP)
    return seconds / trace["step"]["executions"] * 1e6
