"""``native_unpacks_per_dispatch`` (PR 52) on hand-made sources, on
test_fetch_readers.py's pattern: the counter of resolves whose reply
columns the one native pass built, over the door's dispatches — 1.0 from
a scrape pair that has the counter, None from a program without it (the
parent, which the driver runs with these files laid over it: its own
recorded scrapes are the last case)."""

import os

import pytest

from chipbench import promtext, runner
from chipbench.layers import fetches_per_dispatch, native_unpacks_per_dispatch
from chipbench.tests.test_fetch_readers import scrape as fetch_scrape

read = native_unpacks_per_dispatch.read


def scrape(dispatches=None, fetches=(), unpacks=()) -> dict:
    """test_fetch_readers.scrape's lines plus the new family, one value
    a shard label."""
    base = fetch_scrape(dispatches, fetches)
    extra = promtext.parse("\n".join(
        f'rate_limiter_result_native_unpacks_total{{shard="{shard}"}} {value}'
        for shard, value in enumerate(unpacks)))
    return {**base, **extra}


def test_it_sits_beside_the_fetch_counter():
    meta = native_unpacks_per_dispatch.META
    same = ("unit", "layer", "moves", "source")
    assert ({k: meta[k] for k in same}
            == {k: fetches_per_dispatch.META[k] for k in same})
    assert meta["better"] == "higher"
    assert meta["applies"] is fetches_per_dispatch.META["applies"]
    manifest = runner.load_cell("mesh4-exact-80m")["manifest"]
    listed = {m["name"]: m for m in manifest["per_layer"]}
    entry = listed[meta["name"]]
    assert entry["workloads"] == listed["fetches_per_dispatch"]["workloads"]
    assert {k: entry[k] for k in same + ("better",)} == {
        k: meta[k] for k in same + ("better",)}
    assert manifest["per_layer"][-1] is entry       # appended, nothing moved


def test_it_is_the_counter_over_the_doors_dispatches():
    one_chip = {"metrics_start": scrape(1000, [1000], [1000]),
                "metrics_end": scrape(9000, [9000], [9000])}
    assert read(one_chip) == 1.0
    # Four slice shards, each its own label: the sum over them.
    mesh = {"metrics_start": scrape(400, unpacks=[100, 100, 100, 100]),
            "metrics_end": scrape(1200, unpacks=[290, 310, 300, 300])}
    assert read(mesh) == 1.0
    # The collective router: four fetches a frame, ONE rebuild.
    coll = {"metrics_start": scrape(10, [40], [10]),
            "metrics_end": scrape(110, [440], [110])}
    assert read(coll) == 1.0
    assert fetches_per_dispatch.read(coll) == 4.0
    # A host that could not build the extension serves from the twin.
    twin = {"metrics_start": scrape(0, [0], [0]),
            "metrics_end": scrape(50, [50], [0])}
    assert read(twin) == 0.0


def test_it_is_none_on_a_program_without_the_counter():
    parent = {"metrics_start": scrape(1000, [1000]),
              "metrics_end": scrape(9000, [9000])}
    assert read(parent) is None
    assert fetches_per_dispatch.read(parent) == 1.0
    idle = {"metrics_start": scrape(9000, unpacks=[9000]),
            "metrics_end": scrape(9000, unpacks=[9000])}
    assert read(idle) is None
    assert read({"metrics_start": {}, "metrics_end": {}}) is None


@pytest.mark.parametrize("run", ["pr37_mesh_traced", "pr37_wide_traced",
                                 "pr44_ttl_traced"])
def test_it_is_none_on_the_parents_recorded_scrapes(run):
    """Scrapes the chip recorded from programs before PR 52: the fetch
    counter is there, this one is not."""
    data = os.path.join(os.path.dirname(__file__), "data", run)
    recorded = {}
    for key, name in (("metrics_start", "metrics_window_start.txt"),
                      ("metrics_end", "metrics_window_end.txt")):
        with open(os.path.join(data, name)) as fh:
            recorded[key] = promtext.parse(fh.read())
    assert fetches_per_dispatch.read(recorded) == pytest.approx(1.0, abs=0.01)
    assert read(recorded) is None
