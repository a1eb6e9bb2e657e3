"""Table accesses the windowed sketch step made, per row it decided: the
difference of ``rate_limiter_sketch_access_runs_total`` over the
difference of ``rate_limiter_sketch_rows_total`` between the window's
scrapes. A step that carries the mechanism (padded batches of 8,192 rows
and more, ``ops/sortmerge._use_run_dedup``) sorts its batch by key, reads
and writes the count-min table once per run of equal (h1, h2) in that
order — d gathers and d scatters a run, where the per-row step makes
them a row — and ships [padded rows, runs] home in the packed result's
tail words; the limiter sums them at resolve and ``MetricsDecorator``
exports them at scrape, recorder on or off. 1.0: every row its own key,
the per-row step's accesses; ~0.23 on Zipf(1.1) over 262,144 keys at
65,536 rows a dispatch. A count; None where the program exports neither
counter (before PR 36) or no such step ran between the scrapes (every
cell of 4,096-row dispatches)."""

from chipbench.layers import _coalesce

ROWS = "rate_limiter_sketch_rows_total"
RUNS = "rate_limiter_sketch_access_runs_total"


def coalesced_windowed(cell: dict) -> bool:
    """A closed-loop cell of the windowed sketch whose door puts several
    frames into one dispatch (``_coalesce.coalescing``): where a dispatch
    can grow to the rows from which the step carries the mechanism."""
    flags = cell["config"]["server_flags"]
    sketch = any(a == "--backend" and b == "sketch"
                 for a, b in zip(flags, flags[1:]))
    return (_coalesce.coalescing(cell) and sketch
            and cell["config"]["algorithm"] != "token_bucket")


META = {"name": "table_accesses_per_row", "unit": "runs/row",
        "better": "lower", "layer": "device step",
        "moves": "decisions_per_s", "source": "program_counter",
        "applies": coalesced_windowed}


def read(sources: dict):
    rows = _coalesce.moved(sources, ROWS)
    runs = _coalesce.moved(sources, RUNS)
    if rows is None or runs is None or rows <= 0:
        return None
    return runs / rows
