"""The thread-state readers (PR 37; chipbench/layers/_threads.py and the
twelve readers built on it): on scrapes made by hand, on programs without
the counters and spans (every recorded run before PR 37), on the two
traced runs recorded on the chip from the PR's committed files
(data/pr37_wide_traced: one dispatch unit; data/pr37_mesh_traced:
mesh4-hashed-mixed, a door of four), and their manifest entries."""

import json
import os

import pytest

from chipbench import promtext, runner
from chipbench.layers import (
    _threads,
    completer_cpu_pct,
    completer_gil_wait_us_per_dispatch,
    completer_gil_wait_us_per_dispatch_open,
    dispatch_accounted_pct,
    dispatch_covered_pct,
    dispatcher_cpu_pct,
    dispatcher_gather_us_per_dispatch,
    dispatcher_gil_wait_us_per_dispatch,
    dispatcher_gil_wait_us_per_dispatch_open,
    dispatcher_idle_us_per_dispatch,
    dispatcher_idle_us_per_dispatch_open,
    dispatcher_slot_wait_us_per_dispatch,
    stage_us,
    unpack_us_per_dispatch,
)
from chipbench.tests.test_manifest import listed_entry
from chipbench.tests.test_recorded_runs import sources_of

#: The twelve readers, in the order PR 37 listed them.
READERS = (dispatcher_idle_us_per_dispatch,
           dispatcher_idle_us_per_dispatch_open,
           dispatcher_gather_us_per_dispatch,
           dispatcher_gil_wait_us_per_dispatch,
           dispatcher_gil_wait_us_per_dispatch_open,
           dispatcher_slot_wait_us_per_dispatch,
           completer_gil_wait_us_per_dispatch,
           completer_gil_wait_us_per_dispatch_open,
           dispatcher_cpu_pct, completer_cpu_pct,
           unpack_us_per_dispatch, dispatch_accounted_pct)
CLOSED = [r for r in READERS if not r.META["name"].endswith("_open")]
#: Recorded on the chip by this PR: (fixture, cell, dispatch units).
RUNS = [("pr37_wide_traced", "wide-hashed-sat", 1),
        ("pr37_mesh_traced", "mesh4-hashed-mixed", 4)]
#: Recorded before it: programs with neither family and none of the spans.
OLD_RUNS = [("pr32_c3_traced", "c3-hashed-sat"),
            ("pr32_coll_traced", "mesh4-coll-mixed"),
            ("pr33_exact_traced", "exact-hashed-sat"),
            ("pr35_big_traced", "wide-hashed-big")]
DISPATCHER = ("idle", "gather", "gil", "python", "slot", "other")
COMPLETER = ("idle", "gil", "python", "other")


@pytest.fixture(autouse=True)
def published(monkeypatch):
    monkeypatch.setattr(runner, "REHEARSAL", False)


def scrape(dispatches: int, cpu: dict, **states) -> dict:
    """A /metrics scrape of a door that completed ``dispatches`` with its
    threads' seconds as given (``dispatcher_gil=1.5``)."""
    lines = [f"{_threads.DISPATCHES} {dispatches}"]
    for key, seconds in states.items():
        thread, state = key.split("_")
        lines.append(f'{_threads.STATES}{{state="{state}",'
                     f'thread="{thread}"}} {seconds}')
    for thread, seconds in cpu.items():
        lines.append(f'{_threads.CPU}{{thread="{thread}"}} {seconds}')
    return promtext.parse("\n".join(lines))


# ------------------------------------------------------ made by hand

def test_a_state_is_divided_by_dispatches_not_by_wall():
    """Four dispatcher threads over a 20 s window are 80 thread-seconds:
    each state's sum over the units, over the dispatches they made."""
    start = scrape(1_000, {"dispatcher": 10.0, "completer": 2.0},
                   dispatcher_idle=40.0, dispatcher_gather=0.1,
                   dispatcher_gil=8.0, dispatcher_python=30.0,
                   dispatcher_slot=0.0, dispatcher_other=1.9,
                   completer_idle=60.0, completer_gil=5.0,
                   completer_python=14.0, completer_other=1.0)
    end = scrape(1_000 + 8_000, {"dispatcher": 10.0 + 24.0,
                                 "completer": 2.0 + 4.0},
                 dispatcher_idle=40.0 + 4.0, dispatcher_gather=0.1 + 0.4,
                 dispatcher_gil=8.0 + 20.0, dispatcher_python=30.0 + 52.0,
                 dispatcher_slot=0.0, dispatcher_other=1.9 + 3.6,
                 completer_idle=60.0 + 50.0, completer_gil=5.0 + 8.0,
                 completer_python=14.0 + 20.0, completer_other=1.0 + 2.0)
    sources = {"metrics_start": start, "metrics_end": end}
    got = {r.META["name"]: r.read(sources) for r in READERS}
    assert got["dispatcher_idle_us_per_dispatch"] == pytest.approx(500.0)
    assert got["dispatcher_gather_us_per_dispatch"] == pytest.approx(50.0)
    assert got["dispatcher_gil_wait_us_per_dispatch"] == pytest.approx(2500.0)
    assert got["dispatcher_slot_wait_us_per_dispatch"] == 0.0
    assert got["completer_gil_wait_us_per_dispatch"] == pytest.approx(1000.0)
    # CPU over the thread's own wall (its states' sum: 4 x 20 s).
    assert got["dispatcher_cpu_pct"] == pytest.approx(100 * 24.0 / 80.0)
    assert got["completer_cpu_pct"] == pytest.approx(100 * 4.0 / 80.0)
    for name in ("dispatcher_idle", "dispatcher_gil_wait",
                 "completer_gil_wait"):
        assert got[f"{name}_us_per_dispatch_open"] \
            == got[f"{name}_us_per_dispatch"]
    # No ring in these scrapes: the two span readers have nothing.
    assert got["unpack_us_per_dispatch"] is None
    assert got["dispatch_accounted_pct"] is None
    # Nothing completed between the scrapes: nothing to divide by.
    still = {"metrics_start": end, "metrics_end": end}
    for reader in READERS:
        assert reader.read(still) is None, reader.META["name"]


def test_one_family_without_the_other():
    states_only = scrape(100, {}, dispatcher_python=1.0, completer_idle=1.0)
    sources = {"metrics_start": {}, "metrics_end": states_only}
    assert dispatcher_gil_wait_us_per_dispatch.read(sources) == 0.0
    assert dispatcher_cpu_pct.read(sources) is None
    cpu_only = scrape(100, {"dispatcher": 1.0})
    sources = {"metrics_start": {}, "metrics_end": cpu_only}
    assert dispatcher_cpu_pct.read(sources) is None
    assert dispatcher_idle_us_per_dispatch.read(sources) is None


# ------------------------------------------- programs before PR 37

@pytest.mark.parametrize("run, cell", OLD_RUNS)
@pytest.mark.parametrize("reader", READERS,
                         ids=[r.META["name"] for r in READERS])
def test_a_program_without_them_gives_nothing_and_does_not_raise(
        reader, run, cell):
    sources = sources_of(run, cell)
    families = {name for name, _ in sources["metrics_end"]}
    assert not families & {_threads.STATES, _threads.CPU}
    assert reader.read(sources) is None
    # dispatch_covered_pct, which dispatch_accounted_pct builds on, reads
    # those programs as it did.
    assert dispatch_covered_pct.read(sources) is not None


# --------------------------------------------- recorded on the chip

@pytest.mark.parametrize("run, cell, units", RUNS)
def test_every_new_reader_gives_a_number_on_the_new_program(run, cell,
                                                            units):
    sources = sources_of(run, cell)
    for reader in CLOSED:
        assert reader.META["applies"](sources["cell"])
        value = reader.read(sources)
        assert isinstance(value, float) and value >= 0.0, reader.META["name"]
    printed = json.loads(open(os.path.join(
        os.path.dirname(__file__), "data", run, "result_line.json")).read())
    assert printed["correct"] is True and printed["failed"] == 0
    got = runner.per_layer(sources["cell"], sources)
    for name, entry in printed["metrics"].items():
        assert got[name] == (entry["value"], entry["unit"]), name
    assert set(got) == set(printed["metrics"])
    assert {r.META["name"] for r in CLOSED} <= set(got)


@pytest.mark.parametrize("run, cell, units", RUNS)
def test_the_states_tile_the_window_once_per_dispatch_unit(run, cell, units):
    """Each thread's states sum to the seconds between the scrapes, once
    for every dispatcher (completer) thread the door has — which is why
    the readers divide by dispatches: the mesh's sums are four walls."""
    sources = sources_of(run, cell)
    a, b = sources["metrics_start"], sources["metrics_end"]
    for thread, states in (("dispatcher", DISPATCHER),
                           ("completer", COMPLETER)):
        labelled = {dict(labels)["state"] for name, labels in b
                    if name == _threads.STATES
                    and dict(labels)["thread"] == thread}
        assert labelled == set(states)
        wall = promtext.delta(a, b, _threads.STATES, thread=thread)
        assert wall == pytest.approx(units * sources["scrape_s"], rel=0.02)
        cpu = promtext.delta(a, b, _threads.CPU, thread=thread)
        assert 0 < cpu <= wall
    assert {dict(labels)["thread"] for name, labels in b
            if name == _threads.CPU} == {"io", "dispatcher", "completer",
                                         "responder"}


@pytest.mark.parametrize("run, cell, units", RUNS)
def test_the_counters_split_the_rings_stages(run, cell, units):
    """gather + gil is what `enter` holds up to the callback's first
    line, and `slot` is inside `leave`: the ring's means are a sample of
    the window's end (+-10 %), the counters every dispatch."""
    sources = sources_of(run, cell)
    enter = stage_us(sources, "enter")
    split = dispatcher_gather_us_per_dispatch.read(sources) \
        + dispatcher_gil_wait_us_per_dispatch.read(sources)
    assert split <= enter * 1.10
    assert dispatcher_slot_wait_us_per_dispatch.read(sources) \
        <= stage_us(sources, "leave") * 1.10
    accounted = dispatch_accounted_pct.read(sources)
    assert dispatch_covered_pct.read(sources) < accounted <= 105.0
    assert stage_us(sources, "unpack") < stage_us(sources, "fetch") \
        + stage_us(sources, "unpack")


# ------------------------------------------------------ the manifest

def test_their_manifest_entries_and_where_they_apply():
    with open(os.path.join(runner.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = [w["name"] for w in bench["workloads"]]
    reports = {m["name"]: m.get("workloads", cells)
               for m in bench["end_to_end"]}
    entries = []
    for reader in READERS:
        # A predicate over the cell, never a list of names: the cells it
        # applies to are the cells that report the metric it moves.
        entry, on = listed_entry(reader)
        assert on == reports[entry["moves"]]
        entries.append(entry)
    assert {e["source"] for e in entries[:-2]} == {"program_counter"}
    assert {e["source"] for e in entries[-2:]} == {"program_span"}
