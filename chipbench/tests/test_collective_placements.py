"""``collective_placements_per_dispatch`` (PR 45) on hand-made scrapes."""

import pytest

from chipbench import promtext, runner
from chipbench.layers import (_collective, collective_fallback_pct,
                              collective_placements_per_dispatch as reader)


def scrape(dispatches=None, placements=None) -> dict:
    lines = []
    if dispatches is not None:
        lines.append('rate_limiter_collective_dispatches_total{shard="0"} '
                     f'{dispatches}')
    if placements is not None:
        lines.append('rate_limiter_collective_placements_total{shard="0"} '
                     f'{placements}')
    return promtext.parse("\n".join(lines))


def test_it_applies_where_the_fallback_share_does():
    assert reader.META["applies"] is _collective.collective_closed
    assert reader.META["applies"] is collective_fallback_pct.META["applies"]
    assert reader.META["layer"] == "collective routing"
    assert reader.META["moves"] == "decisions_per_s"
    assert reader.META["source"] == "program_counter"
    on = [name for name in ("mesh4-coll-mixed", "mesh4-hashed-mixed",
                            "wide-hashed-sat", "wide-string-rpc")
          if reader.META["applies"](runner.load_cell(name))]
    assert on == ["mesh4-coll-mixed"]


@pytest.mark.parametrize("shards, want", [(4, 4.0), (16, 16.0)])
def test_it_is_the_difference_of_both_counters(shards, want):
    sources = {"metrics_start": scrape(100, 100 * shards),
               "metrics_end": scrape(500, 500 * shards)}
    assert reader.read(sources) == want


def test_a_launch_that_placed_but_failed_shows_above_the_device_count():
    sources = {"metrics_start": scrape(0, 0),
               "metrics_end": scrape(400, 1612)}
    assert reader.read(sources) == pytest.approx(4.03)


def test_none_on_a_program_without_the_counter_and_on_an_idle_window():
    old = {"metrics_start": scrape(100), "metrics_end": scrape(500)}
    assert reader.read(old) is None          # a checkout before PR 45
    idle = {"metrics_start": scrape(500, 2000),
            "metrics_end": scrape(500, 2000)}
    assert reader.read(idle) is None
    assert reader.read({"metrics_start": {}, "metrics_end": {}}) is None
