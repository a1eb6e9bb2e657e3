"""The plain reference against sequences worked by hand."""

from chipbench import reference
from chipbench.reference import MICRO


def test_sliding_window_counts_down_and_denies_at_the_limit():
    ref = reference.SlidingWindow(limit=3, window_s=60, sub_windows=60)
    t = 1_000 * MICRO
    assert ref.allow("a", t) == (True, 2)
    assert ref.allow("a", t) == (True, 1)
    assert ref.allow("b", t) == (True, 2)            # keys are apart
    assert ref.allow("a", t + 5 * MICRO) == (True, 0)
    assert ref.allow("a", t + 6 * MICRO) == (False, 0)
    assert ref.count("a", t + 6 * MICRO) == 3        # a denial adds nothing


def test_sliding_window_forgets_a_sub_window_after_the_window():
    ref = reference.SlidingWindow(limit=3, window_s=60, sub_windows=60)
    t = 1_000 * MICRO
    for at in (0, 0, 5):
        assert ref.allow("a", t + at * MICRO)[0]
    # Second 1000's two requests leave at second 1060, the third at 1065.
    assert ref.allow("a", t + 59 * MICRO) == (False, 0)
    assert ref.allow("a", t + 60 * MICRO) == (True, 1)
    assert ref.allow("a", t + 60 * MICRO) == (True, 0)
    assert ref.allow("a", t + 64 * MICRO) == (False, 0)
    assert ref.allow("a", t + 65 * MICRO) == (True, 0)


def test_sliding_window_cost_n():
    ref = reference.SlidingWindow(limit=10, window_s=60, sub_windows=60)
    assert ref.allow("a", 0, n=7) == (True, 3)
    assert ref.allow("a", 0, n=4) == (False, 3)
    assert ref.allow("a", 0, n=3) == (True, 0)


def test_token_bucket_burst_then_refill():
    # Burst 4, refill 4 per 60 s = one token per 15 s.
    ref = reference.TokenBucket(limit=4, window_s=60)
    t = 500 * MICRO
    assert [ref.allow("a", t) for _ in range(5)] == [
        (True, 3), (True, 2), (True, 1), (True, 0), (False, 0)]
    assert ref.allow("a", t + 14 * MICRO) == (False, 0)
    assert ref.allow("a", t + 15 * MICRO) == (True, 0)
    # 30 s later two tokens are back; a long idle refills to the burst only.
    assert ref.allow("a", t + 45 * MICRO) == (True, 1)
    assert ref.allow("a", t + 10_000 * MICRO) == (True, 3)


def test_token_bucket_interval_holds_both_instants():
    # limit 100 / 60 s: 1.67 tokens per second. Debt 100 at t0.
    ref = reference.TokenBucket(limit=100, window_s=60)
    t0 = 0
    ref.advance("a", t0, t0)
    for _ in range(100):
        (may_deny, may_allow), _ = ref.bounds("a")
        assert may_allow and not may_deny
        ref.apply("a", True)
    assert ref.bounds("a")[0] == (True, False)
    # A frame sent 0.3 s and answered 0.9 s later: at 0.3 s half a token
    # is back (deny), at 0.9 s one and a half (allow). Both are right.
    ref.advance("a", t0 + 300_000, t0 + 900_000)
    (may_deny, may_allow), (least, most) = ref.bounds("a")
    assert may_deny and may_allow and (least, most) == (0, 0)
    ref.apply("a", True)                     # the server allowed: debt >= 100
    assert ref.bounds("a")[0] == (True, False)
    # Sent after 2 s: a token is back whichever instant the server used.
    ref.advance("a", t0 + 2_000_000, t0 + 2_100_000)
    assert ref.bounds("a")[0] == (False, True)


def test_make_knows_the_served_algorithms():
    assert isinstance(reference.make("tpu_sketch", 100, 60, 60),
                      reference.SlidingWindow)
    assert isinstance(reference.make("token_bucket", 100, 60),
                      reference.TokenBucket)
