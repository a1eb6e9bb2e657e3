"""chipbench/promtext.py's two counts the correctness check rests on, on
scrape text as the program's registry renders it."""

import pytest

from chipbench import promtext, runner

#: What ``MetricsDecorator._observe_error`` leaves on /metrics for ONE
#: ``StorageUnavailableError`` (rendered by the program's own registry,
#: PR 50): the failure is told in both families.
ONE_STORAGE_ERROR = """\
# TYPE rate_limiter_requests_total counter
rate_limiter_requests_total{algorithm="token_bucket",result="mixed"} 81920
rate_limiter_requests_total{algorithm="token_bucket",result="error:storage_unavailable"} 1
# HELP rate_limiter_storage_errors_total Backend failures (fail-open allowances included)
# TYPE rate_limiter_storage_errors_total counter
rate_limiter_storage_errors_total{algorithm="token_bucket"} 1
"""
GEN = {"run_s": 23.0, "top_allowed": [100],
       "all": {"completed": 81920, "policy": 0, "error_frames": 1}}
CFG = {"algorithm": "token_bucket", "limit": 100, "window_s": 60}
COLD = {"sent": 4096, "denied": 0, "policy": 0, "cold_false_deny_pct": 0.0}


def unseen(text: str, gen: dict = GEN) -> float:
    samples = promtext.parse(text)
    held = runner.held_numbers(
        CFG, {"sent": 9}, gen, COLD, promtext.policy_answered(samples),
        promtext.dispatch_errors(samples), 9 + 81920 + 4096)
    assert [row[0] for row in held] == [
        "probe_replies_differing", "hot_key_allowed_max",
        "cold_false_deny_pct", "cold_policy_answers",
        "policy_answers_unseen", "dispatch_errors_unseen",
        "decisions_server_short"]
    return dict((name, value) for name, value, _, _ in held)[
        "dispatch_errors_unseen"]


def test_one_failed_dispatch_counts_once():
    """PR 47 was refused ``dispatch_errors_unseen`` 1.0 for this scrape:
    one error frame, which the generator had seen."""
    samples = promtext.parse(ONE_STORAGE_ERROR)
    assert promtext.dispatch_errors(samples) == 1
    assert promtext.policy_answered(samples) == 0
    assert unseen(ONE_STORAGE_ERROR) == 0
    # The frame the generator did NOT see still fails the run.
    blind = dict(GEN, all=dict(GEN["all"], error_frames=0))
    assert unseen(ONE_STORAGE_ERROR, blind) == 1


@pytest.mark.parametrize("extra, errors", [
    ('rate_limiter_requests_total{result="error:invalid_n"} 2\n', 3),
    ('rate_limiter_requests_total{result="fail_open"} 4096\n', 1),
    ("", 1),
], ids=["other-kinds-add", "fail-open-is-policys", "alone"])
def test_every_kind_of_error_is_counted_and_none_twice(extra, errors):
    samples = promtext.parse(ONE_STORAGE_ERROR + extra)
    assert promtext.dispatch_errors(samples) == errors


def test_unlike_failures_in_one_run_do_not_hide_each_other():
    """One error frame of another kind and one fail-open dispatch are two
    failures (the larger of the two families read 1, and with the one
    error frame the generator saw, nothing unseen)."""
    text = ('rate_limiter_requests_total{result="error:invalid_n"} 1\n'
            'rate_limiter_storage_errors_total{algorithm="a"} 1\n')
    assert promtext.dispatch_errors(promtext.parse(text)) == 2
    assert unseen(text) == 1
    # ... beside a storage error told in both families: three, not four.
    assert promtext.dispatch_errors(
        promtext.parse(ONE_STORAGE_ERROR + text)) == 3


def test_a_dispatch_answered_by_policy_alone_is_still_held():
    """``storage_errors_total`` without an error frame (a fail-open
    batch) is held against the run."""
    text = ('rate_limiter_storage_errors_total{algorithm="a"} 2\n'
            'rate_limiter_requests_total{result="fail_open"} 8192\n')
    samples = promtext.parse(text)
    assert promtext.dispatch_errors(samples) == 2
    assert promtext.policy_answered(samples) == 8192
    assert promtext.dispatch_errors(promtext.parse("")) == 0
