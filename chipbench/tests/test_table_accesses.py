"""``table_accesses_per_row`` (PR 36): the reader on scrapes made by hand,
on a program without the counters, and its manifest entry — beside
test_exact_cell.py's and test_big_cell.py's reader tests."""

import json
import os

from chipbench import promtext, runner
from chipbench.layers import table_accesses_per_row as reader
from chipbench.tests.test_manifest import listed_entry

CELL = "wide-hashed-big"


def scrape(**values) -> dict:
    return promtext.parse("\n".join(f"{name} {value}"
                                    for name, value in values.items()))


def test_the_reader_on_counters_made_by_hand():
    start = scrape(**{reader.ROWS: 1_048_576, reader.RUNS: 300_000})
    end = scrape(**{reader.ROWS: 1_048_576 + 10 * 65_536,
                    reader.RUNS: 300_000 + 10 * 14_800})
    sources = {"metrics_start": start, "metrics_end": end}
    assert reader.read(sources) == 14_800 / 65_536
    # Every row its own key: the per-row step's accesses.
    flat = scrape(**{reader.ROWS: 1_048_576 + 65_536,
                     reader.RUNS: 300_000 + 65_536})
    assert reader.read({"metrics_start": start, "metrics_end": flat}) == 1.0
    # No step that carries the mechanism ran between the scrapes (every
    # cell of 4,096-row dispatches: the counters stand still at 0).
    still = {"metrics_start": end, "metrics_end": end}
    assert reader.read(still) is None
    zeros = scrape(**{reader.ROWS: 0, reader.RUNS: 0})
    assert reader.read({"metrics_start": zeros, "metrics_end": zeros}) is None


def test_a_program_without_the_counters_gives_nothing_and_does_not_raise():
    """The parent's /metrics (any run recorded before PR 36) has neither
    counter; one of the two alone is nothing to divide either."""
    from chipbench.tests.test_recorded_runs import sources_of

    for fixture, cell in (("pr32_c3_traced", "c3-hashed-sat"),
                          ("pr35_big_traced", CELL)):
        assert reader.read(sources_of(fixture, cell)) is None
    half = scrape(**{reader.ROWS: 65_536})
    assert reader.read({"metrics_start": scrape(), "metrics_end": half}) \
        is None


def test_its_manifest_entry_and_where_it_applies():
    with open(os.path.join(runner.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    # A predicate over the cell, never a list of names: closed loop, the
    # windowed sketch, several frames a dispatch.
    entry, on = listed_entry(reader)
    assert entry == {"name": "table_accesses_per_row", "unit": "runs/row",
                     "better": "lower", "source": "program_counter",
                     "layer": "device step", "moves": "decisions_per_s",
                     "workloads": [CELL]}
    assert on == [CELL]
    big = runner.load_cell(CELL)
    flags = [a if a != "sketch" else "dense"
             for a in big["config"]["server_flags"]]
    for other in (dict(big, traffic=dict(big["traffic"], loop="open")),
                  dict(big, traffic=dict(big["traffic"], frame_keys=65536)),
                  dict(big, config=dict(big["config"], server_flags=flags)),
                  dict(big, config=dict(big["config"],
                                        algorithm="token_bucket"))):
        assert not reader.META["applies"](other)
    assert any(m["name"] == "decisions_per_s" and CELL in m["workloads"]
               for m in bench["end_to_end"])
