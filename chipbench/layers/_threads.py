"""What the thread-state readers share: the door's two always-on counter
families (PR 37) and the divisions made of them.

``rate_limiter_door_thread_seconds_total{thread,state}``: wall time the
native door's dispatcher and completer threads have spent in each state —
C++ atomics added to where a thread changes state, recorder on or off,
summed over dispatch units, exported at scrape. The states of a thread
tile its loop (dispatcher: idle, gather, gil, python, slot, other;
completer: idle, gil, python, other), so a thread's states sum to its
wall. ``rate_limiter_door_thread_cpu_seconds_total{thread}``: the CPU
clocks of the door's threads by role (io, dispatcher, completer,
responder), read at scrape.

A state is reported per DISPATCH (its seconds over
``rate_limiter_door_dispatches_total``, both between the window's
scrapes), never per second of wall: four dispatcher threads of a mesh
door sum to four walls, and a dispatch is what each of them is paced by.
None where the program exports no such family (before PR 37) or the door
completed nothing between the scrapes."""

from chipbench import promtext

STATES = "rate_limiter_door_thread_seconds_total"
CPU = "rate_limiter_door_thread_cpu_seconds_total"
DISPATCHES = "rate_limiter_door_dispatches_total"


def _exported(samples: dict, family: str) -> bool:
    return any(name == family for name, _ in samples)


def state_us(sources: dict, thread: str, state: str):
    """Microseconds a dispatch that ``thread`` spent in ``state``."""
    a, b = sources["metrics_start"], sources["metrics_end"]
    if not _exported(b, STATES):
        return None
    dispatches = promtext.delta(a, b, DISPATCHES)
    if dispatches <= 0:
        return None
    seconds = promtext.delta(a, b, STATES, thread=thread, state=state)
    return seconds / dispatches * 1e6


def cpu_pct(sources: dict, thread: str):
    """100 x the CPU seconds of ``thread`` over its states' sum (its
    wall), both between the scrapes: the share of its time the thread
    computes; the rest it is blocked — for the GIL, a lock, a transfer,
    a queue."""
    a, b = sources["metrics_start"], sources["metrics_end"]
    if not (_exported(b, STATES) and _exported(b, CPU)):
        return None
    wall = promtext.delta(a, b, STATES, thread=thread)
    if wall <= 0:
        return None
    return 100.0 * promtext.delta(a, b, CPU, thread=thread) / wall
