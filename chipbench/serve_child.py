"""The server child: ``python -m ratelimiter_tpu.serving`` run in this
process, unchanged, plus one thing the program does not report — the
device's peak memory, read from JAX when the server has shut down and
written to the file named first on the command line.

The runner never imports JAX; this process is the only one that holds
the chip, so it is the only one that can ask.
"""

from __future__ import annotations

import json
import runpy
import sys


def _device_report() -> dict:
    jax = sys.modules.get("jax")
    if jax is None:
        return {"devices": []}
    rows = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        rows.append({"id": dev.id, "platform": dev.platform,
                     "kind": dev.device_kind,
                     "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                     "bytes_limit": stats.get("bytes_limit")})
    return {"devices": rows}


def main() -> None:
    report_path = sys.argv[1]
    sys.argv = ["ratelimiter_tpu.serving"] + sys.argv[2:]
    try:
        runpy.run_module("ratelimiter_tpu.serving", run_name="__main__",
                         alter_sys=True)
    finally:
        with open(report_path, "w") as fh:
            json.dump(_device_report(), fh)


if __name__ == "__main__":
    main()
