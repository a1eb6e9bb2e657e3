"""How chipbench/tests/data/step.xplane.pb was made (PR 22, on the chip):

    chiprun -- python3 -m chipbench.tests.record_fixture wide-hashed-sat

Starts the cell's server as a run does, drives it with the cell's own
traffic, takes a 0.1 s profile through /debug/profile and keeps the raw
.xplane.pb under chiprun_out/. chipbench/tests/cut_xplane.py then cuts
it to a few milliseconds.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time
import urllib.request

from chipbench import runner


def main() -> int:
    cell = runner.load_cell(sys.argv[1])
    out_dir = os.path.join(runner.HERE, "out", "fixture")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    binary, _ = runner.build_loadgen()
    with runner.serving(cell, out_dir, trace=True) as srv:
        gen = runner.Generator(binary, cell, 1, 12.0)
        schedule = gen.start(srv.port)
        time.sleep(max(0.0, schedule["t_window_start"] + 1.0
                       - time.monotonic()))
        for seconds in (0.1, 0.1):      # the first capture pays start-up
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.http_port}/debug/profile"
                    f"?seconds={seconds}", timeout=120) as resp:
                profile = json.loads(resp.read())
        gen.result()
    keep = os.path.join(runner.ROOT, "chiprun_out", "fixture")
    os.makedirs(keep, exist_ok=True)
    for name in profile["files"]:
        if name.endswith(".xplane.pb"):
            shutil.copy(os.path.join(profile["dir"], name),
                        os.path.join(keep, f"{sys.argv[1]}.xplane.pb"))
    shutil.rmtree(os.path.join(out_dir, "tmp"), ignore_errors=True)
    print(os.listdir(keep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
