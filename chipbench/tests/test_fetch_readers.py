"""The resolve half's readers (PR 29) on hand-made sources: the ``fetch``
span and the fetch counter over the door's dispatches, each None on a
program that has neither (the parent, which the driver runs with these
files laid over it)."""

import pytest

from chipbench import promtext, runner
from chipbench.layers import (fetch_us_per_dispatch,
                              fetch_us_per_dispatch_open,
                              fetches_per_dispatch,
                              fetches_per_dispatch_open)

CLOSED = [fetch_us_per_dispatch, fetches_per_dispatch]
OPEN = [fetch_us_per_dispatch_open, fetches_per_dispatch_open]


def scrape(dispatches=None, fetches=(), stages=()) -> dict:
    """A parsed /metrics scrape with the given cumulative values;
    ``fetches``: one value per shard label; ``stages``: (stage, count,
    seconds)."""
    lines = []
    if dispatches is not None:
        lines.append(f"rate_limiter_door_dispatches_total {dispatches}")
    for shard, value in enumerate(fetches):
        lines.append('rate_limiter_result_fetches_total'
                     f'{{shard="{shard}"}} {value}')
    for stage, count, seconds in stages:
        lines.append(f'rate_limiter_stage_seconds_count{{stage="{stage}"}} '
                     f'{count}')
        lines.append(f'rate_limiter_stage_seconds_sum{{stage="{stage}"}} '
                     f'{seconds}')
    return promtext.parse("\n".join(lines))


def test_they_apply_by_the_loop_and_sit_in_the_resolve_layer():
    cells = {name: runner.load_cell(name) for name in (
        "wide-hashed-sat", "bucket-hashed-sat", "mesh4-hashed-mixed",
        "mesh4-coll-mixed", "wide-string-rpc")}
    for reader in CLOSED + OPEN:
        on = {n for n, c in cells.items() if reader.META["applies"](c)}
        assert on == ({"wide-string-rpc"} if reader in OPEN
                      else set(cells) - {"wide-string-rpc"})
        assert reader.META["layer"] == "resolve / pack"
        assert reader.META["moves"] == ("latency_p50_ms" if reader in OPEN
                                        else "decisions_per_s")
    assert fetch_us_per_dispatch.META["source"] == "program_span"
    assert fetches_per_dispatch.META["source"] == "program_counter"


@pytest.mark.parametrize("reader", [fetch_us_per_dispatch,
                                    fetch_us_per_dispatch_open])
def test_fetch_us_is_the_mean_of_the_stage_between_the_scrapes(reader):
    sources = {"metrics_start": scrape(stages=[("fetch", 100, 0.010)]),
               "metrics_end": scrape(stages=[("fetch", 500, 0.060),
                                             ("finish", 500, 9.0)])}
    assert reader.read(sources) == pytest.approx(0.050 / 400 * 1e6)
    # A program without the span (the parent), and a window in which no
    # ticket was resolved.
    old = {"metrics_start": scrape(stages=[("finish", 1, 0.1)]),
           "metrics_end": scrape(stages=[("finish", 9, 0.9)])}
    assert reader.read(old) is None
    still = dict(sources, metrics_start=sources["metrics_end"])
    assert reader.read(still) is None


@pytest.mark.parametrize("reader", [fetches_per_dispatch,
                                    fetches_per_dispatch_open])
def test_fetches_is_the_counter_over_the_doors_dispatches(reader):
    one_chip = {"metrics_start": scrape(1000, fetches=[1000]),
                "metrics_end": scrape(9000, fetches=[9000])}
    assert reader.read(one_chip) == 1.0
    # Four slice shards, each its own label: the sum over them.
    mesh = {"metrics_start": scrape(400, fetches=[100, 100, 100, 100]),
            "metrics_end": scrape(1200, fetches=[290, 310, 300, 300])}
    assert reader.read(mesh) == 1.0
    # The collective router: one door dispatch a frame, a shard a chip.
    coll = {"metrics_start": scrape(10, fetches=[40]),
            "metrics_end": scrape(110, fetches=[440])}
    assert reader.read(coll) == 4.0
    # The parent's four leaves a dispatch.
    parent = {"metrics_start": scrape(0, fetches=[0]),
              "metrics_end": scrape(50, fetches=[200])}
    assert reader.read(parent) == 4.0


@pytest.mark.parametrize("reader", [fetches_per_dispatch,
                                    fetches_per_dispatch_open])
def test_fetches_is_none_without_its_counter_or_without_dispatches(reader):
    old = {"metrics_start": scrape(1000), "metrics_end": scrape(9000)}
    assert reader.read(old) is None
    idle = {"metrics_start": scrape(9000, fetches=[9000]),
            "metrics_end": scrape(9000, fetches=[9000])}
    assert reader.read(idle) is None
    assert reader.read({"metrics_start": {}, "metrics_end": {}}) is None
