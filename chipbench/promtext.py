"""Prometheus text exposition -> numbers: the reader of the server's
/metrics scrape. ``parse`` gives {(family, labels): value}; the helpers
answer what the per-layer readers and the correctness check ask."""

from __future__ import annotations

import re

_LINE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line)
        if m:
            labels = tuple(sorted(_LABEL.findall(m.group(2) or "")))
            try:
                out[(m.group(1), labels)] = float(m.group(3))
            except ValueError:
                pass
    return out


def total(samples: dict, family: str, **labels) -> float:
    """Sum of every sample of ``family`` whose labels include ``labels``
    (a value may be a compiled regex)."""
    acc = 0.0
    for (name, have), value in samples.items():
        if name != family:
            continue
        have = dict(have)
        if all(k in have and (v.fullmatch(have[k]) if hasattr(v, "fullmatch")
                              else have[k] == v)
               for k, v in labels.items()):
            acc += value
    return acc


def delta(start: dict, end: dict, family: str, **labels) -> float:
    return total(end, family, **labels) - total(start, family, **labels)


def mean_of(start: dict, end: dict, family: str, **labels):
    """Mean of a histogram family between two scrapes (sum / count), or
    None when nothing was observed in between."""
    n = delta(start, end, family + "_count", **labels)
    if n <= 0:
        return None
    return delta(start, end, family + "_sum", **labels) / n


_POLICY = (("rate_limiter_requests_total", {"result": "fail_open"}),
           ("rate_limiter_server_slo_breach_decisions_total", {}),
           ("rate_limiter_breaker_short_circuits_total", {}))
_ANY_ERROR = re.compile("error:.*")


def policy_answered(samples: dict) -> float:
    """Decisions the server answered by its fail-open/closed policy."""
    return sum(total(samples, fam, **lab) for fam, lab in _POLICY)


def dispatch_errors(samples: dict) -> float:
    """Failed dispatches, each counted ONCE: the two families' sum less
    the one failure both tell. ``MetricsDecorator._observe_error`` adds
    one to ``requests_total{result="error:<kind>"}`` for every call that
    raised and, where the kind is ``storage_unavailable``, one more to
    ``storage_errors_total`` — so the plain sum read 2 for one error
    frame (ledger, PR 47: ``dispatch_errors_unseen`` 1.0 for a frame the
    generator had seen). Everything else either family holds is a failure
    of its own and adds: an error of another kind (``requests_total``
    alone), a dispatch answered by the fail-open policy
    (``storage_errors_total`` alone, no error frame). Not the larger of
    the two: one error frame of another kind and one fail-open dispatch
    in the same run are two failures, and the larger read 1."""
    requests = "rate_limiter_requests_total"
    return (total(samples, requests, result=_ANY_ERROR)
            + total(samples, "rate_limiter_storage_errors_total")
            - total(samples, requests, result="error:storage_unavailable"))
