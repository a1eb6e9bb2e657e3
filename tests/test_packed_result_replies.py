"""The doors' replies against the parent's, for one recorded frame set
(ISSUE 29, satellite (d)).

``tests/data/packed_result/parent_replies.npz`` holds what commit 7a28f20
— four result columns fetched leaf by leaf, retry/reset computed in the
step — wrote for the frames of ``record_replies.py``: the native door's
five reply parts and the asyncio door's whole hashed-lane frame, one chip
and the collective router, both rules. The packed result must give the
same bytes wherever the parent's bytes were exact:

* flags, ``remaining``, ``limit`` and the wire frame's header and allow
  mask: byte for byte;
* ``retry_after`` / ``reset_at``: the IEEE float64 quotient of the integer
  microseconds, which is at most one unit in the last place from the
  parent's value — under jit XLA's CPU backend turns ``x / 1e6`` into ``x *
  1e-6`` (so does the TPU's emulated f64), which is not correctly rounded;
  where the two differ the new value is the correctly rounded one.
"""

import importlib.util
import os

import numpy as np
import pytest

import jax

HERE = os.path.join(os.path.dirname(__file__), "data", "packed_result")
_spec = importlib.util.spec_from_file_location(
    "record_replies", os.path.join(HERE, "record_replies.py"))
record = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(record)

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 (virtual) devices")


@pytest.fixture(scope="module")
def both():
    with np.load(os.path.join(HERE, "parent_replies.npz")) as fh:
        parent = {name: fh[name] for name in fh.files}
    ours = record.replies()
    assert sorted(ours) == sorted(parent)
    return parent, ours


def _tags():
    return [f"{rule}/{unit}/{k}/{size}" for rule in record.RULES
            for unit in record.UNITS for k in range(len(record.INSTANTS))
            for size in record.SIZES]


def _assert_ieee_within_one_ulp(ours: np.ndarray, parent: np.ndarray):
    """Both are float64 columns of microseconds / 1e6."""
    us = np.rint(ours * 1e6)
    np.testing.assert_array_equal(ours.view(np.int64),
                                  (us / 1e6).view(np.int64))
    ulps = np.abs(ours.view(np.int64) - parent.view(np.int64))
    assert ulps.max(initial=0) <= 1
    return int((ulps != 0).sum())


@pytest.mark.parametrize("tag", _tags())
def test_native_door_reply_parts(both, tag):
    parent, ours = both
    for part in ("flags", "remaining", "limit"):
        np.testing.assert_array_equal(ours[f"native/{tag}/{part}"],
                                      parent[f"native/{tag}/{part}"],
                                      err_msg=part)
    for part in ("retry_after", "reset_at"):
        _assert_ieee_within_one_ulp(
            ours[f"native/{tag}/{part}"].view(np.float64),
            parent[f"native/{tag}/{part}"].view(np.float64))


@pytest.mark.parametrize("tag", _tags())
def test_hashed_wire_lane_reply_frame(both, tag):
    """The frame is ``header | allow mask | remaining i64[b] | retry f64[b]
    | reset f64[b]``: everything before the two float columns is the
    parent's byte for byte, the columns are IEEE and within one ulp."""
    parent, ours = both
    a, b = ours[f"wire/{tag}"], parent[f"wire/{tag}"]
    assert a.shape == b.shape
    n = int(tag.rsplit("/", 1)[1])
    floats = 16 * n
    np.testing.assert_array_equal(a[:-floats], b[:-floats])
    _assert_ieee_within_one_ulp(a[-floats:].view(np.float64),
                                b[-floats:].view(np.float64))


def test_the_float_columns_that_moved_are_counted(both):
    """How far from 'byte-identical' the replies are, kept as a number:
    of the native parts' float values, the share one ulp off the parent's
    (always toward the correctly rounded quotient, asserted above)."""
    parent, ours = both
    moved = total = 0
    for tag in _tags():
        for part in ("retry_after", "reset_at"):
            a = ours[f"native/{tag}/{part}"].view(np.float64)
            moved += _assert_ieee_within_one_ulp(
                a, parent[f"native/{tag}/{part}"].view(np.float64))
            total += a.shape[0]
    assert 0 < moved < total // 2
