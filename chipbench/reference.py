"""The plain reference: what a rate limiter must answer.

A dict per key and integer arithmetic with an explicit ``now``; it
imports nothing from ratelimiter_tpu. Two rules, the two the cells serve:

SlidingWindow  a ring of ``sub_windows`` counters per key, each covering
               ``window / sub_windows`` seconds. A request is allowed
               while the counts of the sub-windows still inside the
               window, the current one included, sum to at most
               ``limit - n``; an allowed request adds n to the current
               sub-window, a denied one adds nothing. ``remaining`` is
               the limit less that sum after the request.

TokenBucket    burst ``limit``, refilled continuously at ``limit /
               window`` tokens per second, kept as a debt in
               micro-tokens that decays toward 0 (tokens = limit -
               debt). Allowed while debt + n <= limit; a denial takes
               nothing. The served bucket reads its own clock between
               the instants a frame is sent and answered, so the
               reference keeps the INTERVAL of debts those instants
               allow, and a served answer is right when it lies inside.
"""

from __future__ import annotations

MICRO = 1_000_000


class SlidingWindow:
    def __init__(self, limit: int, window_s: int, sub_windows: int):
        self.limit, self.sub_windows = limit, sub_windows
        self.sub_us = window_s * MICRO // sub_windows
        self._keys: dict = {}   # key -> {sub-window index: count}

    def count(self, key, now_us: int) -> int:
        """Admitted mass of ``key`` still inside the window at ``now``."""
        ring = self._keys.get(key, {})
        cur = now_us // self.sub_us
        for idx in [i for i in ring if i <= cur - self.sub_windows]:
            del ring[idx]
        return sum(ring.values())

    def allow(self, key, now_us: int, n: int = 1):
        """(allowed, remaining) for one request of cost n at ``now``."""
        used = self.count(key, now_us)
        if used + n > self.limit:
            return False, max(0, self.limit - used)
        ring = self._keys.setdefault(key, {})
        cur = now_us // self.sub_us
        ring[cur] = ring.get(cur, 0) + n
        return True, self.limit - used - n


class TokenBucket:
    """Debt intervals [lo, hi] in micro-tokens, per key."""

    def __init__(self, limit: int, window_s: int):
        self.limit_u, self.window_us = limit * MICRO, window_s * MICRO
        # key -> [lo, hi, earliest, latest instant of the last request]
        self._keys: dict = {}
        self._rem: dict = {}     # exact mode: decay remainder per key

    def _decay(self, elapsed_us: int) -> int:
        return max(0, elapsed_us) * self.limit_u // self.window_us

    def advance(self, key, sent_us: int, replied_us: int) -> None:
        """Move ``key`` to a frame sent at ``sent`` and answered at
        ``replied``: the server applied it somewhere in between."""
        lo, hi, early, late = self._keys.get(key, (0, 0, sent_us, sent_us))
        self._keys[key] = [
            max(0, lo - self._decay(replied_us - early) - 1),
            max(0, hi - self._decay(sent_us - late)),
            sent_us, replied_us]

    def bounds(self, key, n: int = 1):
        """((may be denied, may be allowed), (least, most remaining if
        allowed)) for the next request of ``key`` inside the frame."""
        lo, hi = self._keys[key][:2]
        need = n * MICRO
        return ((hi + need > self.limit_u, lo + need <= self.limit_u),
                ((self.limit_u - min(hi, self.limit_u - need) - need) // MICRO,
                 (self.limit_u - lo - need) // MICRO))

    def apply(self, key, allowed: bool, n: int = 1) -> None:
        """Fold the served answer back in: it narrows the interval."""
        st = self._keys[key]
        need = n * MICRO
        if allowed:
            st[0], st[1] = st[0] + need, min(st[1], self.limit_u - need) + need
        else:
            st[0] = max(st[0], self.limit_u - need + 1)
            st[1] = max(st[1], st[0])

    def allow(self, key, now_us: int, n: int = 1):
        """The exact rule at one instant (no interval): (allowed,
        remaining). The fraction of a micro-token a decay leaves over is
        carried to the next, so no refill is lost to rounding."""
        debt, _, _, last = self._keys.get(key, (0, 0, now_us, now_us))
        num = max(0, now_us - last) * self.limit_u + self._rem.get(key, 0)
        debt = max(0, debt - num // self.window_us)
        self._rem[key] = num % self.window_us if debt else 0
        allowed = debt + n * MICRO <= self.limit_u
        if allowed:
            debt += n * MICRO
        self._keys[key] = [debt, debt, now_us, now_us]
        return allowed, (self.limit_u - debt) // MICRO


def make(algorithm: str, limit: int, window_s: int, sub_windows: int = 60):
    if algorithm == "token_bucket":
        return TokenBucket(limit, window_s)
    if algorithm in ("tpu_sketch", "sliding_window"):
        return SlidingWindow(limit, window_s, sub_windows)
    raise ValueError(f"no reference for algorithm {algorithm!r}")
