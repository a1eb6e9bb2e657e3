"""The collective router's readers (PR 27) on hand-made sources, and
``chipbench/bytes_ici.py`` against counts made by hand."""

import json
import os

import pytest

from chipbench import bytes_ici, promtext, runner
from chipbench.layers import (_collective, assemble_us_per_dispatch,
                              collective_fallback_pct, exchange_roofline,
                              exchange_us_per_dispatch,
                              writeback_us_per_dispatch)

READERS = [collective_fallback_pct, assemble_us_per_dispatch,
           writeback_us_per_dispatch, exchange_us_per_dispatch,
           exchange_roofline]
with open(os.path.join(runner.HERE, "peaks.json")) as _fh:
    PEAKS = json.load(_fh)["TPU v5 lite"]


def scrape(dispatches=None, overflow=None, strict=None, stages=(),
           batch=None) -> dict:
    """A parsed /metrics scrape with the given cumulative values;
    ``stages``: (stage, count, seconds); ``batch``: (count, sum)."""
    lines = []
    if dispatches is not None:
        lines.append('rate_limiter_collective_dispatches_total{shard="0"} '
                     f'{dispatches}')
    for reason, value in (("overflow", overflow), ("strict", strict)):
        if value is not None:
            lines.append('rate_limiter_collective_fallbacks_total'
                         f'{{reason="{reason}",shard="0"}} {value}')
    for stage, count, seconds in stages:
        lines.append(f'rate_limiter_stage_seconds_count{{stage="{stage}"}} '
                     f'{count}')
        lines.append(f'rate_limiter_stage_seconds_sum{{stage="{stage}"}} '
                     f'{seconds}')
    if batch is not None:
        lines.append(f"rate_limiter_server_batch_size_count {batch[0]}")
        lines.append(f"rate_limiter_server_batch_size_sum {batch[1]}")
    return promtext.parse("\n".join(lines))


def trace(executions=400, ops=(("fusion", 0.3), ("all_to_all", 0.008))):
    return {"step": {"module": "jit_routed_sketch_step",
                     "executions": executions, "seconds": 0.31},
            "n_devices": 4, "window_s": 5.0,
            "device_ops": [list(row) for row in ops]}


def test_they_apply_by_the_routers_flag_and_the_loop_never_by_name():
    cells = {name: runner.load_cell(name) for name in (
        "mesh4-coll-mixed", "mesh4-hashed-mixed", "wide-hashed-sat",
        "wide-string-rpc")}
    for reader in READERS:
        on = [n for n, c in cells.items() if reader.META["applies"](c)]
        assert on == ["mesh4-coll-mixed"], reader.META["name"]
        assert reader.META["layer"] == "collective routing"
        assert reader.META["moves"] == "decisions_per_s"
    cell = dict(cells["mesh4-coll-mixed"], name="anything-else")
    assert _collective.collective_closed(cell)
    opened = dict(cell, traffic=dict(cell["traffic"], loop="open"))
    assert not _collective.collective_closed(opened)
    hosted = dict(cell, config=dict(cell["config"], server_flags=[
        "--native", "--router", "host"]))
    assert not _collective.collective_closed(hosted)
    last = dict(cell, config=dict(cell["config"],
                                  server_flags=["--native", "--router"]))
    assert not _collective.collective_closed(last)


def test_fallback_share_is_the_difference_of_both_counters():
    sources = {"metrics_start": scrape(100, overflow=1, strict=0),
               "metrics_end": scrape(500, overflow=4, strict=1)}
    assert collective_fallback_pct.read(sources) == pytest.approx(
        100.0 * (3 + 1) / 400)
    sources["metrics_end"] = scrape(500, overflow=1, strict=0)
    assert collective_fallback_pct.read(sources) == 0.0


@pytest.mark.parametrize("start, end", [
    ({}, {}),                                      # the parent: no counters
    (scrape(7, overflow=0), scrape(7, overflow=0)),   # nothing launched
    ({}, scrape(overflow=2)),                      # fallbacks alone
], ids=["no-counters", "no-dispatch", "no-dispatch-counter"])
def test_fallback_share_without_dispatches_is_none(start, end):
    assert collective_fallback_pct.read(
        {"metrics_start": start, "metrics_end": end}) is None


@pytest.mark.parametrize("reader, stage", [
    (assemble_us_per_dispatch, "assemble"),
    (writeback_us_per_dispatch, "writeback")], ids=["assemble", "writeback"])
def test_stage_readers_are_the_rings_mean(reader, stage):
    other = "writeback" if stage == "assemble" else "assemble"
    sources = {
        "metrics_start": scrape(stages=[(stage, 10, 0.001),
                                        (other, 10, 0.5)]),
        "metrics_end": scrape(stages=[(stage, 110, 0.026),
                                      (other, 110, 0.9)])}
    assert reader.read(sources) == pytest.approx(250.0)      # 25 ms / 100
    # A program without the span (the parent), or no new row in between.
    assert reader.read({"metrics_start": {}, "metrics_end": {}}) is None
    assert reader.read({"metrics_start": sources["metrics_end"],
                        "metrics_end": sources["metrics_end"]}) is None


def test_exchange_time_is_the_all_to_all_groups_over_step_executions():
    # 8 ms over four chips' 400 executions of the step: 20 us each.
    assert exchange_us_per_dispatch.read({"trace": trace()}) == \
        pytest.approx(20.0)
    both = trace(ops=(("fusion", 0.3), ("all-to-all-start", 0.001),
                      ("all-to-all-done", 0.003), ("all_to_all", 0.004),
                      ("pmax", 0.002), ("all-reduce", 0.5)))
    assert exchange_us_per_dispatch.read({"trace": both}) == \
        pytest.approx(20.0)


@pytest.mark.parametrize("sources", [
    {}, {"trace": None}, {"trace": {}},
    {"trace": trace(ops=(("fusion", 0.3), ("sort", 0.1)))},
    {"trace": dict(trace(), step=None)},
    {"trace": trace(executions=0)},
], ids=["no-key", "untraced", "empty", "not-among-the-ten", "no-step",
        "no-executions"])
def test_exchange_readers_with_nothing_to_read_are_none(sources):
    assert exchange_us_per_dispatch.read(sources) is None
    full = {"metrics_start": scrape(batch=(0, 0)),
            "metrics_end": scrape(batch=(100, 409600)), "peaks": PEAKS,
            "cell": runner.load_cell("mesh4-coll-mixed"), **sources}
    assert exchange_roofline.read(full) is None


def test_bytes_on_ici_by_hand():
    # n = 4 chips, L = 1,024 rows a chip: 768 rows belong elsewhere.
    assert bytes_ici.rows_leaving(1024, 4) == 768
    # Out: u64 id + u32 n = 12 B a row. Back: 1 bit + i64 remaining.
    assert bytes_ici.exchange_bytes("tpu_sketch", 1024, 4) == \
        768 * 12 + 768 * 8.125 == 15456.0
    # The bucket's retry_after is per key and crosses too.
    assert bytes_ici.exchange_bytes("token_bucket", 1024, 4) == \
        768 * 12 + 768 * 16.125
    assert bytes_ici.exchange_bytes("tpu_sketch", 1024, 1) == 0.0


def test_roofline_share_by_hand_and_far_under_one_percent():
    sources = {"trace": trace(), "peaks": PEAKS,
               "cell": runner.load_cell("mesh4-coll-mixed"),
               "metrics_start": scrape(batch=(0, 0)),
               "metrics_end": scrape(batch=(100, 409600))}
    # 4,096 a dispatch over 4 chips: 15,456 B at 200 GB/s = 0.07728 us of
    # need against 20 us on the device.
    assert exchange_roofline.read(sources) == pytest.approx(
        100.0 * (15456 / 200e9) / 20e-6)
    assert exchange_roofline.read(sources) < 1.0
    assert exchange_roofline.read(dict(sources, peaks=None)) is None
    unknown = dict(PEAKS, name="a device the table lacks")
    assert exchange_roofline.read(dict(sources, peaks=unknown)) is None
    no_batch = dict(sources, metrics_end=scrape(batch=(0, 0)))
    assert exchange_roofline.read(no_batch) is None


def test_the_ici_rate_has_its_source_and_says_it_is_assumed():
    with open(os.path.join(runner.HERE, "peaks_ici.json")) as fh:
        table = json.load(fh)
    row = table["TPU v5 lite"]
    assert row["ici_bytes_per_s"] == 1600e9 / 8
    assert "Google Cloud" in row["source"] and row["assumed"]
    assert exchange_roofline.ici_peak(PEAKS) == row
    assert "cpu" not in table
