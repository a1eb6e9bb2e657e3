"""chipbench/bytes.py against counts made by hand."""

import json
import os

import pytest

from chipbench import bytes as need

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cfg(name):
    """The configuration as the manifest names its file."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        files = {c["name"]: c["file"] for c in json.load(fh)["configs"]}
    with open(os.path.join(ROOT, files[name])) as fh:
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


#: The "sketch" model on the benchmark's own configurations, computed by
#: the parent's bytes.py (commit 9bfe964, before the model became a name):
#: (step_bytes(4096, 0), step_bytes(4096, 582.5), step_bytes(2933.25,
#: 191.75), step_ops(4096)). The four old files name no model and are
#: byte-identical to the parent's; the numbers may not move by a bit.
PINNED = {
    "cms-wide": (442880.0, 572489.3939914163, 710886.323785854, 151552),
    "bucket-c3": (803328.0, 803328.0, 575283.65625, 180224),
    "mesh4-c3": (541184.0, 551984.782832618, 420366.3785446545, 180224),
    "mesh4-c3-coll": (541184.0, 551984.782832618, 420366.3785446545,
                      180224),
    # PR 32's own (configs[2]'s geometry is mesh4-c3's slice).
    "cms-c3": (541184.0, 551984.782832618, 420366.3785446545, 180224),
}


@pytest.mark.parametrize("name", list(PINNED))
def test_the_sketch_model_is_unchanged_to_the_last_bit(name):
    c = cfg(name)
    assert "byte_model" not in c
    assert need.model_of(c) is None
    assert (need.step_bytes(c, 4096, 0), need.step_bytes(c, 4096, 582.5),
            need.step_bytes(c, 2933.25, 191.75),
            need.step_ops(c, 4096)) == PINNED[name]


@pytest.mark.parametrize("lacking", ["depth", "width"])
def test_no_model_and_no_sketch_geometry_is_said_in_words(lacking):
    c = cfg("cms-wide")
    del c[lacking]
    with pytest.raises(need.NoByteModel, match="byte_model") as err:
        need.step_bytes(c, 4096, 0)
    assert lacking in str(err.value) and not isinstance(err.value, KeyError)
    with pytest.raises(need.NoByteModel):
        need.step_ops(c, 4096)


@pytest.mark.parametrize("name", ["no-such-model", "../bytes", "a b", 7, ""])
def test_a_model_that_is_no_module_or_no_name_is_refused(name):
    c = dict(cfg("cms-wide"), byte_model=name)
    with pytest.raises(need.NoByteModel):
        need.step_bytes(c, 4096, 0)


#: The exact table's need a decision, whichever of its two model names a
#: configuration gives ("table"; "reclaim" adds the pass's bytes, not a
#: second arithmetic — PR 50): 12 B of probe + 3 x 16 B of row + 12 B in
#: + 24.125 B out.
EXACT = {"exact-tb-1m": "table", "exact-tb-20m": "table",
         "exact-tb-ttl": "reclaim"}


@pytest.mark.parametrize("name", list(EXACT))
def test_the_exact_table_moves_96_125_bytes_a_decision(name):
    from chipbench import bytes_reclaim, bytes_table

    c = cfg(name)
    assert c["byte_model"] == EXACT[name]       # each keeps the one it names
    assert need.step_bytes(c, 1, 0.0) == 96.125
    assert need.step_bytes(c, 4096, 570.0) == 393728.0
    assert need.step_ops(c, 4096) == 4096 * 31
    assert bytes_reclaim.step_bytes is bytes_table.step_bytes
    # No premise on the table's size: the population's hundredfold, in a
    # table that holds it or in one sized for the active set.
    for capacity, keys in ((1 << 28, 10 ** 8), (1 << 21, 10 ** 8)):
        big = dict(c, capacity=capacity, key_population=keys)
        assert need.step_bytes(big, 4096, 570.0) == 393728.0
    assert bytes_reclaim.pass_bytes(c, 1000) \
        == c["capacity"] * 16 + 1000 * 32
