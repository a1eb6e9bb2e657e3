"""A snapshot does not stall the served path (persistence/snapshotter.py,
docs/ADR/009): only the device→host capture takes the limiter's lock;
serialization, the fsynced write and the manifest commit run without
it, while decisions keep flowing.

Held here with events, never a clock: the snapshot's host-side write is
parked on an event the test controls, and while it is parked the same
limiter must launch and resolve, its lock must be free, its decisions
must be those of a twin that never snapshots, and the file that lands
must restore to the state at the capture — not to whatever the limiter
went on to decide while the write was open (on a CPU ``np.asarray`` of a
device array can be a view of a buffer the next step is donated).
"""

import os
import threading
from dataclasses import replace

import numpy as np
import pytest

from ratelimiter_tpu import (
    Algorithm,
    Config,
    DenseParams,
    ManualClock,
    PersistenceSpec,
    SketchParams,
    create_limiter,
)
from ratelimiter_tpu.observability.metrics import Registry
from ratelimiter_tpu.persistence import PersistenceManager, snapshotter

T0 = 1_700_000_000.0
#: A liveness guard, not a budget: a defect here is a deadlock, and the
#: wait only bounds how long a broken tree hangs before it fails.
GUARD_S = 120.0
RULES = {
    "sketch": dict(algorithm=Algorithm.SLIDING_WINDOW,
                   sketch=SketchParams(depth=3, width=512, sub_windows=6)),
    "dense": dict(algorithm=Algorithm.TOKEN_BUCKET,
                  dense=DenseParams(capacity=256)),
}


def _frame(i: int) -> np.ndarray:
    """64 ids, a quarter of them one hot key: frames deny as well as
    allow, and every frame touches keys the one before it touched."""
    rng = np.random.default_rng(i)
    ids = rng.integers(1, 40, size=64, dtype=np.uint64)
    ids[::4] = 7
    return ids


def _columns(res) -> tuple:
    return tuple(np.asarray(getattr(res, f)).tolist()
                 for f in ("allowed", "remaining", "retry_after", "reset_at"))


class _Held:
    """A limiter behind the persistence manager, a twin on the same
    stream that never snapshots, and one snapshot parked inside the
    host-side call named ``stage``."""

    def __init__(self, backend, stage, tmp_path, monkeypatch):
        cfg = Config(limit=5, window=60.0,
                     persistence=PersistenceSpec(dir=str(tmp_path),
                                                 snapshot_interval=1000.0),
                     **RULES[backend])
        self.backend, self.cfg, self.dir = backend, cfg, str(tmp_path)
        self.clock, self.twin_clock = ManualClock(T0), ManualClock(T0)
        self.mgr = PersistenceManager(cfg.persistence, registry=Registry())
        self.lim = self.mgr.wrap(
            create_limiter(cfg, backend=backend, clock=self.clock))
        self.mgr.attach([self.lim])
        self.twin = create_limiter(replace(cfg, persistence=PersistenceSpec()),
                                   backend=backend, clock=self.twin_clock)
        self.sent = 0
        self.entered, self.release = threading.Event(), threading.Event()
        real = getattr(snapshotter, stage)

        def parked(*a, **kw):
            self.entered.set()
            assert self.release.wait(GUARD_S)
            return real(*a, **kw)

        monkeypatch.setattr(snapshotter, stage, parked)
        self.entry = None
        self.thread = threading.Thread(target=self._snapshot, daemon=True)

    def _snapshot(self):
        self.entry = self.mgr.snapshot_now()

    def decide(self, n: int) -> list:
        """n frames through launch_hashed / resolve of both limiters;
        returns [(served columns, twin columns)]."""
        out = []
        for _ in range(n):
            ids = _frame(self.sent)
            self.sent += 1
            for c in (self.clock, self.twin_clock):
                c.advance(0.25)
            out.append(tuple(
                _columns(lim.resolve(lim.launch_hashed(ids)))
                for lim in (self.lim, self.twin)))
        return out

    def park(self):
        self.thread.start()
        assert self.entered.wait(GUARD_S), "the snapshot never reached " \
            "its host-side write"

    def finish(self) -> dict:
        self.release.set()
        self.thread.join(GUARD_S)
        assert not self.thread.is_alive() and self.entry is not None
        return self.entry

    def close(self):
        self.release.set()
        if self.thread.is_alive():
            self.thread.join(GUARD_S)
        self.mgr.stop(final_snapshot=False)
        self.lim.close()
        self.twin.close()


@pytest.fixture
def held(request, tmp_path, monkeypatch):
    backend, stage = request.param
    h = _Held(backend, stage, tmp_path, monkeypatch)
    yield h
    h.close()


def _cases(stages=("save_state",)):
    return pytest.mark.parametrize(
        "held", [(b, s) for b in RULES for s in stages], indirect=True,
        ids=lambda p: f"{p[0]}-{p[1]}")


@_cases()
def test_launch_and_resolve_complete_while_the_write_is_held_open(held):
    held.decide(3)
    held.park()
    done = threading.Event()
    got = []

    def serve():
        got.extend(held.decide(4))
        done.set()

    threading.Thread(target=serve, daemon=True).start()
    assert done.wait(GUARD_S), "launch_hashed / resolve waited for a " \
        "snapshot's write"
    # ... and they completed DURING the snapshot, not after it.
    assert held.thread.is_alive() and held.entry is None
    assert len(got) == 4
    assert held.finish()["files"]


@_cases(stages=("save_state", "write_manifest"))
def test_the_limiters_lock_is_free_while_the_snapshot_serialises_and_writes(
        held):
    held.decide(2)
    held.park()
    lock = held.lim.inner._lock
    # From THIS thread: the snapshot thread is parked inside the stage,
    # so a lock it still held would refuse a non-blocking acquire.
    assert lock.acquire(blocking=False), \
        "the limiter's lock is held across the snapshot's host-side write"
    lock.release()
    assert not held.mgr._log_lock.locked()
    held.finish()


@_cases()
def test_decisions_during_a_snapshot_equal_a_run_without_one(held):
    before = held.decide(3)
    held.park()
    during = held.decide(5)
    held.finish()
    after = held.decide(3)
    for served, twin in before + during + after:
        assert served == twin
    # The stream is not trivially all-allowed: the hot key is denied.
    assert any(not all(served[0]) for served, _ in during)


@_cases()
def test_the_snapshot_restores_to_the_state_at_its_cut(held):
    held.decide(4)
    _, cut, _ = held.twin.capture_state()
    cut = {k: np.array(v, copy=True) for k, v in cut.items()}
    held.park()
    held.decide(5)                      # decided while the write is open
    entry = held.finish()
    _, now_arrays, _ = held.lim.capture_state()
    assert any(not np.array_equal(now_arrays[k], cut[k]) for k in cut), \
        "the frames decided during the write changed nothing: the test " \
        "cannot tell the cut from the end"

    fresh = create_limiter(held.cfg, backend=held.backend,
                           clock=ManualClock(held.clock.now()))
    try:
        fresh.restore(os.path.join(held.dir, entry["files"][0]))
        _, restored, _ = fresh.capture_state()
        assert set(restored) == set(cut)
        for k in cut:
            np.testing.assert_array_equal(restored[k], cut[k], err_msg=k)
    finally:
        fresh.close()
