"""``programs_per_dispatch`` on recorded traces. The old program's
(data/old_program: PR 22's server) launched five programs a dispatch —
the step, the finish program and three eager scalar conversions — and
the reader must say so when it is laid over such a checkout; a trace
with the step alone reads 1."""

import json
import os

import pytest

from chipbench.layers import programs_per_dispatch as closed
from chipbench.layers import programs_per_dispatch_open as opened

DATA = os.path.join(os.path.dirname(__file__), "data", "old_program")


def _old_trace() -> dict:
    with open(os.path.join(DATA, "trace_reduced.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("reader", [closed, opened],
                         ids=["closed", "open"])
def test_the_old_program_launched_five_a_dispatch(reader):
    trace = _old_trace()
    by_name = {name: count for name, count, _ in trace["modules"]}
    assert by_name == {"jit__unknown": 1029, "jit_finish_window": 1024,
                       "jit_convert_element_type": 3077}
    got = reader.read({"trace": trace})
    assert got == pytest.approx((1029 + 1024 + 3077) / 1029)
    assert 4.9 < got < 5.1


def test_one_launch_a_dispatch_and_a_rotation_a_second():
    trace = {"step": {"module": "jit_sketch_step", "executions": 1000,
                      "seconds": 1.2},
             "modules": [["jit_sketch_step", 1000, 1.2],
                         ["jit_sketch_rotate", 5, 0.009]]}
    assert closed.read({"trace": trace}) == pytest.approx(1.005)


@pytest.mark.parametrize("sources", [
    {}, {"trace": None}, {"trace": {}}, {"trace": {"step": None}},
    {"trace": {"step": {"executions": 0}, "modules": []}},
], ids=["no-key", "untraced", "empty", "no-step", "no-executions"])
def test_nothing_to_read_is_none(sources):
    assert closed.read(sources) is None
    assert opened.read(sources) is None


def test_the_twin_moves_the_open_loops_metric():
    assert closed.META["moves"] == "decisions_per_s"
    assert opened.META["name"] == "programs_per_dispatch_open"
    assert opened.META["moves"] == "latency_p50_ms"
    assert closed.META["layer"] == opened.META["layer"] == "dispatch"
