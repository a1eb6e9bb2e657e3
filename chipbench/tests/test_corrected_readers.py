"""PR 32's corrections and additions that change no result: the two
readers that read wrong under the collective router only
(``step_roofline``, ``dispatch_covered_pct``), every reader against the
parent's values on the recorded fixture, the runner's ``holes`` line and
the ``compared`` key of the result line."""

import json
import os

import pytest

from chipbench import bytes as need
from chipbench import layers, runner
from chipbench.layers import dispatch_covered_pct, step_roofline
from chipbench.tests import test_readers_old_program as old
from chipbench.tests.test_collective_readers import PEAKS, scrape, trace

CORRECTED = {"step_roofline", "dispatch_covered_pct"}
with open(os.path.join(old.DATA, "readers_at_9bfe964.json")) as _fh:
    #: cell -> reader -> what the PARENT's reader (commit 9bfe964, PR 31)
    #: returns on data/old_program with the half-way first scrape, traced.
    PARENT = json.load(_fh)


def held_to_parent(parent: dict, sources: dict, collective: bool) -> int:
    """Every reader the parent had returns, on ``sources``, what the
    parent's returned (``parent``: reader -> value) bit for bit — but for
    the corrected ones under the collective router, which must differ
    where they read anything. Returns how many numbers were compared."""
    readers = {r.META["name"]: r for r in layers.load()}
    assert set(parent) <= set(readers)
    for name, was in parent.items():
        got = readers[name].read(sources)
        if (collective and was is not None
                and name.removesuffix("_open") in CORRECTED):
            assert got != was, name
        else:
            assert got == was, name
    return sum(was is not None for was in parent.values())


@pytest.mark.parametrize("cell_name", list(PARENT))
def test_every_reader_returns_the_parents_value_bit_for_bit(cell_name):
    sources = old._sources(cell_name, "half", True)
    assert held_to_parent(PARENT[cell_name], sources,
                          cell_name == "mesh4-coll-mixed") >= 15


def stages(count, **us):
    return [(stage, count, count * v * 1e-6) for stage, v in us.items()]


#: mesh4-coll-mixed, traced, PR 31's ring means (PERF.md section 5).
COLL = dict(dispatch=4613, enter=168, prep=89, place=1810, step=1946,
            finish=18, leave=106, assemble=234, writeback=103)


def test_covered_share_counts_assemble_and_writeback_where_recorded():
    sources = {"metrics_start": scrape(stages=stages(0, **COLL)),
               "metrics_end": scrape(stages=stages(1000, **COLL))}
    seven = 168 + 89 + 1810 + 1946 + 18 + 106
    assert dispatch_covered_pct.read(sources) == pytest.approx(
        100.0 * (seven + 234 + 103) / 4613)             # 97.0 %
    assert 100.0 * seven / 4613 == pytest.approx(89.7, abs=0.05)  # before
    # A door without the collective's spans: the same seven as before.
    plain = {k: v for k, v in COLL.items()
             if k not in ("assemble", "writeback")}
    sources = {"metrics_start": scrape(stages=stages(0, **plain)),
               "metrics_end": scrape(stages=stages(1000, **plain))}
    assert dispatch_covered_pct.read(sources) == 100.0 * seven / 4613
    # The string lane's hash is counted as it was.
    hashed = dict(plain, hash=88)
    sources = {"metrics_start": scrape(stages=stages(0, **hashed)),
               "metrics_end": scrape(stages=stages(1000, **hashed))}
    assert dispatch_covered_pct.read(sources) == pytest.approx(
        100.0 * (seven + 88) / 4613)


@pytest.fixture()
def published(monkeypatch):
    """The published geometry even where JAX_PLATFORMS=cpu would make
    ``load_cell`` apply the rehearsal's."""
    monkeypatch.setattr(runner, "REHEARSAL", False)


def roofline_sources(cell_name):
    # 4 chips x 1,000 executions in 5 s; 0.4277 s of device-busy a chip:
    # 427.7 us an execution, 200 dispatches a second a device.
    tr = dict(trace(executions=4000), busy_s=0.4277)
    return {"cell": runner.load_cell(cell_name), "trace": tr, "peaks": PEAKS,
            "metrics_start": scrape(batch=(0, 0)),
            "metrics_end": scrape(batch=(1000, 4096000))}


def test_collective_roofline_is_one_chips_bytes_over_one_chips_time(published):
    sources = roofline_sources("mesh4-coll-mixed")
    cfg = sources["cell"]["config"]
    step_s = 0.4277 * 4 / 4000
    after = 100.0 * need.step_bytes(cfg, 1024, 200.0) / 819e9 / step_s
    before = 100.0 * need.step_bytes(cfg, 4096, 200.0) / 819e9 / step_s
    assert step_roofline.read(sources) == pytest.approx(after)
    assert after == pytest.approx(0.0476, abs=0.0005)
    assert before == pytest.approx(0.1635, abs=0.0005)     # PR 31 read 0.164


@pytest.mark.parametrize("cell_name", ["mesh4-hashed-mixed",
                                       "wide-hashed-sat", "c3-hashed-sat"])
def test_every_other_door_keeps_the_whole_batch(cell_name, published):
    sources = roofline_sources(cell_name)
    cfg = sources["cell"]["config"]
    assert step_roofline.read(sources) == (
        100.0 * (need.step_bytes(cfg, 4096.0, 200.0) / 819e9)
        / (0.4277 * 4 / 4000 * 1e6 * 1e-6))


def test_holes_are_the_seconds_under_half_the_median():
    per_second = [{"completed": n} for n in
                  (2_400_000, 0, 0, 290_000, 2_375_000, 2_380_000,
                   1_185_000, 1_184_999, 2_390_000, 2_375_000, 2_360_000,
                   2_370_000, 2_385_000)]
    assert runner.holes(per_second) == {
        "median_per_s": 2_370_000, "seconds": [1, 2, 3, 7], "held_s": 4}
    steady = [{"completed": 100 + i} for i in range(20)]
    assert runner.holes(steady)["seconds"] == []
    assert runner.holes([]) == {"median_per_s": None, "seconds": [],
                                "held_s": 0}


def test_compared_comes_last_in_the_result_line_each_beside_its_limit():
    gen = {"loop": "closed", "sent": 10, "policy": 0, "error_decisions": 0,
           "unanswered": 0}
    line = runner.result_line(
        True, gen, {}, {"platform": "tpu"}, {"device_ops": [],
                                             "idle_gaps": []},
        {"hot_key_allowed_max": (100, 100), "cold_false_deny_pct": (0.5, 1.0)})
    assert list(line)[-1] == "compared"
    assert line["compared"] == {
        "hot_key_allowed_max": {"value": 100, "limit": 100},
        "cold_false_deny_pct": {"value": 0.5, "limit": 1.0}}
