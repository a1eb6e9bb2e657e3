"""Metric-name drift gate (ADR-021 satellite): OPERATIONS §3 is the
monitoring CONTRACT, so it must match what servers actually export —
in BOTH directions.

Five real server binaries (spawned concurrently) cover the
backend-conditional families:

* a fully-featured windowed-sketch member (fleet + audit + hh +
  flight recorder + breaker + tenants + controller + persistence +
  leases) — the bulk of the families, incl. the sketch accuracy
  envelope and the ADR-022 lease families;
* a mesh member with quarantine — the per-slice failure-domain
  families;
* a token-bucket server behind the NATIVE door — the debt-slab
  families plus the multi-ring network-engine families (ADR-026:
  engine info, syscall ledger, writev batch factor);
* a mesh member behind the collective router (ADR-024) — its dispatch
  and fallback counters;
* a dense (exact, a row a key) token-bucket server behind the native
  door — the device-resident key directory's families (ADR-027).

Direction 1: every `rate_limiter_*` name written in OPERATIONS §3 must
exist in the union scrape (a documented name may also be a PREFIX of a
scraped family — the `rate_limiter_audit_slice_*` glob idiom).
Direction 2: every scraped family must appear somewhere in
OPERATIONS.md. A renamed/dropped/added-but-undocumented metric fails
here instead of silently breaking dashboards.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time
import urllib.request

import pytest

from netutil import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPERATIONS = os.path.join(REPO, "docs", "OPERATIONS.md")


def _spawn(argv_extra, env_extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + env.get("PYTHONPATH", "").split(os.pathsep))
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    argv = [sys.executable, "-m", "ratelimiter_tpu.serving",
            "--sketch-depth", "2", "--sketch-width", "1024",
            "--no-prewarm", "--max-batch", "256", *argv_extra]
    return subprocess.Popen(argv, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True)


def _await_banner(proc):
    line = proc.stdout.readline()
    if "serving" not in line:
        raise RuntimeError(f"server failed to start: {line!r}")


def _scrape(http_port: int) -> str:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{http_port}/metrics", timeout=10) as r:
        return r.read().decode()


def _families(text: str) -> set:
    return set(re.findall(r"# TYPE (\S+) ", text))


@pytest.mark.slow
class TestMetricNameDrift:
    def test_operations_section3_matches_scrape_both_directions(
            self, tmp_path):
        ports = [free_port() for _ in range(5)]
        https = [free_port() for _ in range(5)]
        cfgpath = os.path.join(str(tmp_path), "fleet.json")
        with open(cfgpath, "w", encoding="utf-8") as f:
            json.dump({"buckets": 32, "epoch": 1, "hosts": [
                {"id": "h0", "host": "127.0.0.1", "port": ports[0],
                 "http": https[0], "ranges": [[0, 32]]}]}, f)
        snap = os.path.join(str(tmp_path), "snap")
        procs = [
            # 1: featured windowed-sketch fleet member.
            _spawn(["--backend", "sketch", "--sub-windows", "6",
                    "--port", str(ports[0]),
                    "--http-port", str(https[0]),
                    "--fleet-config", cfgpath, "--fleet-self", "h0",
                    "--flight-recorder", "--debug-token", "tok",
                    "--audit", "--audit-sample", "1",
                    "--hh-slots", "16", "--circuit-breaker",
                    "--tenants", "4", "--global-limit", "1000",
                    "--controller", "--snapshot-dir", snap,
                    "--leases",
                    "--http-rebalance-token", "rtok",
                    "--http-policy-token", "ptok"]),
            # 2: mesh + quarantine (per-slice failure domains).
            _spawn(["--backend", "mesh", "--mesh-devices", "2",
                    "--quarantine", "--sub-windows", "6",
                    "--port", str(ports[1]),
                    "--http-port", str(https[1])],
                   {"XLA_FLAGS":
                    "--xla_force_host_platform_device_count=2"}),
            # 3: token bucket (debt-slab families) behind the NATIVE
            # door (multi-ring net engine families, ISSUE-20).
            _spawn(["--algorithm", "token_bucket", "--backend",
                    "sketch", "--native", "--port", str(ports[2]),
                    "--http-port", str(https[2])]),
            # 4: mesh behind the collective router (its dispatch and
            # fallback counters).
            _spawn(["--backend", "mesh", "--mesh-devices", "2",
                    "--router", "collective", "--sub-windows", "6",
                    "--port", str(ports[3]),
                    "--http-port", str(https[3])],
                   {"XLA_FLAGS":
                    "--xla_force_host_platform_device_count=2"}),
            # 5: the dense backend behind the native door (the key
            # directory's counters).
            _spawn(["--algorithm", "token_bucket", "--backend", "dense",
                    "--dense-capacity", "4096", "--native",
                    "--port", str(ports[4]),
                    "--http-port", str(https[4])]),
        ]
        try:
            for proc in procs:
                _await_banner(proc)
            # One policy mutation: the override-occupancy gauge
            # registers on first set (documented §3 family).
            req = urllib.request.Request(
                f"http://127.0.0.1:{https[0]}/v1/policy?key=k&limit=5",
                method="POST")
            req.add_header("Authorization", "Bearer ptok")
            urllib.request.urlopen(req, timeout=10).read()
            time.sleep(0.3)
            fams = set()
            for hp in https:
                fams |= _families(_scrape(hp))
        finally:
            for proc in procs:
                proc.terminate()
            for proc in procs:
                try:
                    proc.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    proc.kill()

        assert len(fams) > 50, f"suspiciously small scrape: {fams}"
        with open(OPERATIONS, encoding="utf-8") as f:
            ops = f.read()
        sec3 = re.search(r"\n## 3\. What to monitor(.*?)\n## 4\.",
                         ops, re.S).group(1)
        doc3 = set(re.findall(r"rate_limiter_[a-z0-9_]*[a-z0-9]",
                              sec3))

        # Direction 1: everything §3 names is really exported (exact
        # family, or a prefix — the `..._slice_*` glob idiom).
        missing = sorted(
            n for n in doc3
            if n not in fams
            and not any(f.startswith(n + "_") for f in fams))
        assert not missing, (
            f"OPERATIONS §3 documents families no server exports "
            f"(renamed? dropped?): {missing}")

        # Direction 2: everything exported is documented SOMEWHERE in
        # OPERATIONS.md.
        undocumented = sorted(n for n in fams if n not in ops)
        assert not undocumented, (
            f"servers export families OPERATIONS.md never mentions "
            f"(add a §3 row): {undocumented}")
