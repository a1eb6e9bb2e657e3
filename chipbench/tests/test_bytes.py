"""chipbench/bytes.py against counts made by hand."""

import json
import os

import pytest

from chipbench import bytes as need

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as fh:
        return json.load(fh)


def test_windowed_decision_bytes():
    # d=3: per row 2 reads of 4 B and 2 read-modify-writes of 4+4 B = 24 B,
    # 72 B of cells; 12 B in; 24.125 B out.
    assert need.decision_bytes("tpu_sketch", 3) == 72 + 12 + 24.125


def test_bucket_decision_bytes():
    # d=4: per row one 8 B read and two 16 B read-modify-writes = 40 B.
    assert need.decision_bytes("token_bucket", 4) == 160 + 12 + 24.125


def test_rotation_bytes():
    assert need.rotation_bytes("tpu_sketch", 3, 1 << 20) == 6 * 3 * (1 << 20) * 4
    assert need.rotation_bytes("token_bucket", 4, 65536) == 0


def test_step_bytes_of_the_wide_cell():
    wide = cfg("cms-wide")
    per_key = 4096 * 108.125
    assert need.step_bytes(wide, 4096, 0) == per_key
    # One rotation per second (60 sub-windows / 60 s) over 250 dispatches.
    assert need.step_bytes(wide, 4096, 250) == pytest.approx(
        per_key + 75_497_472 / 250)


def test_step_ops():
    assert need.step_ops(cfg("bucket-c3"), 100) == 100 * (16 + 28)
