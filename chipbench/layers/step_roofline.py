"""The device step's share of its roofline: the bytes one dispatch must
move (chipbench/bytes.py) over the peak HBM rate (chipbench/peaks.json),
divided by the device time one dispatch took. Bound by bytes.

The time is ONE chip's execution of the step (device-busy over
executions, both summed over the chips), so the bytes are one chip's
too. Behind the host router a dispatch is one slice's own batch on one
chip. Under ``--router collective`` a dispatch is the whole frame in one
program over every chip, each deciding ``batch / chips`` rows: the
frame's bytes against one chip's time read ``chips`` times high (until
PR 32 they did)."""

from chipbench import bytes as need
from chipbench.layers import _collective, closed_loop
from chipbench.layers import device_us_per_dispatch, dispatch_batch_mean

META = {"name": "step_roofline", "unit": "%", "better": "higher",
        "layer": "device step", "moves": "decisions_per_s",
        "source": "device_trace", "applies": closed_loop}


def read(sources: dict):
    step_us = device_us_per_dispatch.read(sources)
    batch = dispatch_batch_mean.read(sources)
    peaks, trace = sources.get("peaks"), sources.get("trace")
    if not step_us or not batch or not peaks:
        return None
    # Executions are counted over every device plane; a slice rotates its
    # own ring, so the rotations are shared by one device's dispatches.
    per_s = (trace["step"]["executions"] / trace["n_devices"]
             / trace["window_s"])
    cell = sources["cell"]
    if _collective.collective_closed(cell):
        batch /= cell["chips"]
    must = need.step_bytes(cell["config"], batch, per_s)
    return 100.0 * (must / peaks["hbm_bytes_per_s"]) / (step_us * 1e-6)
