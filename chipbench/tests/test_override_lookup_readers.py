"""The override-lookup share's readers (PR 31) on hand-made sources: the
program's counter over the door's dispatches, None on a program that has
no such counter (the parent, which the driver runs with these files laid
over it)."""

import pytest

from chipbench import promtext, runner
from chipbench.layers import override_lookup_pct, override_lookup_pct_open
from chipbench.tests.test_manifest import listed_entry

READERS = [override_lookup_pct, override_lookup_pct_open]
CELLS = ("wide-hashed-sat", "bucket-hashed-sat", "mesh4-hashed-mixed",
         "mesh4-coll-mixed", "wide-string-rpc")


def scrape(dispatches=None, lookups=()) -> dict:
    """A parsed /metrics scrape with the given cumulative values;
    ``lookups``: one value per shard label."""
    lines = []
    if dispatches is not None:
        lines.append(f"rate_limiter_door_dispatches_total {dispatches}")
    for shard, value in enumerate(lookups):
        lines.append('rate_limiter_override_lookup_dispatches_total'
                     f'{{shard="{shard}"}} {value}')
    return promtext.parse("\n".join(lines))


@pytest.mark.parametrize("reader", READERS)
def test_it_applies_by_the_loop_and_sits_in_the_device_step(reader):
    cells = {name: runner.load_cell(name) for name in CELLS}
    on = {n for n, c in cells.items() if reader.META["applies"](c)}
    is_open = reader is override_lookup_pct_open
    assert on == ({"wide-string-rpc"} if is_open
                  else set(cells) - {"wide-string-rpc"})
    assert reader.META["layer"] == "device step"
    assert reader.META["unit"] == "%"
    assert reader.META["source"] == "program_counter"
    assert reader.META["moves"] == ("latency_p50_ms" if is_open
                                    else "decisions_per_s")


@pytest.mark.parametrize("reader", READERS)
def test_the_manifest_lists_it_with_the_cells_it_applies_to(reader):
    _, on = listed_entry(reader)
    is_open = reader is override_lookup_pct_open
    want = {c for c in CELLS if (c == "wide-string-rpc") == is_open}
    assert want <= set(on)                  # with the cells added since
    assert not set(on) & (set(CELLS) - want)


@pytest.mark.parametrize("reader", READERS)
def test_it_is_the_counter_over_the_doors_dispatches_in_percent(reader):
    # No override anywhere: every cell of the benchmark.
    empty = {"metrics_start": scrape(1000, lookups=[0]),
             "metrics_end": scrape(9000, lookups=[0])}
    assert reader.read(empty) == 0.0
    # An override set before the window: every dispatch looks up.
    full = {"metrics_start": scrape(1000, lookups=[1000]),
            "metrics_end": scrape(9000, lookups=[9000])}
    assert reader.read(full) == 100.0
    # Set halfway through the window.
    half = {"metrics_start": scrape(0, lookups=[0]),
            "metrics_end": scrape(400, lookups=[100])}
    assert reader.read(half) == 25.0
    # Four slice shards, each its own label: the sum over them.
    mesh = {"metrics_start": scrape(400, lookups=[100, 100, 100, 100]),
            "metrics_end": scrape(1200, lookups=[290, 310, 300, 300])}
    assert reader.read(mesh) == 100.0
    # The collective router: one door dispatch and one count a frame.
    coll = {"metrics_start": scrape(10, lookups=[10]),
            "metrics_end": scrape(110, lookups=[110])}
    assert reader.read(coll) == 100.0


@pytest.mark.parametrize("reader", READERS)
def test_it_is_none_without_its_counter_or_without_dispatches(reader):
    old = {"metrics_start": scrape(1000), "metrics_end": scrape(9000)}
    assert reader.read(old) is None
    idle = {"metrics_start": scrape(9000, lookups=[0]),
            "metrics_end": scrape(9000, lookups=[0])}
    assert reader.read(idle) is None
    assert reader.read({"metrics_start": {}, "metrics_end": {}}) is None
