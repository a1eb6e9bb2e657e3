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
_ERRORS = (("rate_limiter_requests_total", {"result": re.compile("error:.*")}),
           ("rate_limiter_storage_errors_total", {}))


def policy_answered(samples: dict) -> float:
    """Decisions the server answered by its fail-open/closed policy."""
    return sum(total(samples, fam, **lab) for fam, lab in _POLICY)


def dispatch_errors(samples: dict) -> float:
    return sum(total(samples, fam, **lab) for fam, lab in _ERRORS)
