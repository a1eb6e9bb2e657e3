"""Server binary: ``python -m ratelimiter_tpu.serving``.

Realizes the reference's stub entry point (``cmd/server/main.go:9-18`` —
its TODO list is exactly this file's job): config from flags, limiter
init, serve, graceful shutdown on SIGTERM/SIGINT.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import signal
import time

from ratelimiter_tpu import (
    Algorithm,
    Config,
    DenseParams,
    SketchParams,
    create_limiter,
)
from ratelimiter_tpu.observability import (
    CircuitBreakerDecorator,
    LoggingDecorator,
    MetricsDecorator,
    TracingDecorator,
)
from ratelimiter_tpu.observability import metrics as obs_metrics
from ratelimiter_tpu.serving.server import RateLimitServer


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="ratelimiter_tpu.serving",
        description="TPU-backed rate-limit service")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8432)
    ap.add_argument("--listen", default=None, metavar="ADDR",
                    help="binary-door bind override (ADR-025): "
                         "'unix:/path' listens on a unix domain socket "
                         "instead of TCP (--port ignored for the binary "
                         "door; HTTP/gRPC/lease sidecars keep --host)")
    ap.add_argument("--shm", action="store_true",
                    help="enable the zero-syscall shared-memory wire "
                         "lane (ADR-025): a connected client may send "
                         "T_SHM_HELLO to upgrade its connection to "
                         "per-connection SPSC ring pairs in --shm-dir "
                         "carrying the SAME wire frames; the socket "
                         "stays open as the liveness/control channel. "
                         "Off (the default) = wire bytes byte-identical "
                         "to a server without this flag")
    ap.add_argument("--shm-dir", default="/dev/shm", metavar="DIR",
                    help="--shm: directory for the ring files (0600, "
                         "unlinked after the handshake; same-uid trust "
                         "boundary — see OPERATIONS §6)")
    ap.add_argument("--shm-ring-bytes", type=int, default=0, metavar="B",
                    help="--shm: per-direction ring capacity (power of "
                         "two, clamped to [64KiB, 64MiB]; 0 = 2MiB "
                         "default). A client's hello may request its "
                         "own size; the server clamps")
    ap.add_argument("--algorithm", default="tpu_sketch",
                    choices=[a.value for a in Algorithm])
    ap.add_argument("--backend", default="sketch",
                    choices=["exact", "dense", "sketch", "mesh"],
                    help="state backend; 'mesh' is slice-parallel serving "
                         "(ADR-012): one device-pinned sketch slice per "
                         "visible device, keys hash-routed to their owning "
                         "slice, decide path collective-free")
    ap.add_argument("--mesh-devices", type=int, default=None,
                    help="--backend mesh: devices to span (default: all "
                         "visible; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N). "
                         "--backend dense: mount one exact table per "
                         "device, N of them, a key's row on the chip "
                         "that owns its hash (host router only; unset = "
                         "one table on the default device)")
    ap.add_argument("--router", default="host",
                    choices=["host", "collective"],
                    help="--backend mesh: how a mixed frame reaches its "
                         "owning slices. 'host' (ADR-013) argsorts and "
                         "fans out per-slice sub-launches on the host; "
                         "'collective' (ADR-024) makes the whole frame "
                         "ONE shard_map dispatch — owners computed on "
                         "device, rows routed with all_to_all, the host "
                         "never partitions. Incompatible with "
                         "--quarantine (whole-mesh blast radius)")
    ap.add_argument("--bin-headroom", type=float, default=2.0,
                    help="--router collective: per-(source,destination) "
                         "bin capacity multiplier over the L/n mean; a "
                         "frame overflowing a bin falls back to the host "
                         "router (never silently dropped)")
    ap.add_argument("--quarantine", action="store_true",
                    help="--backend mesh: per-slice failure domains "
                         "(ADR-015) — slice dispatches get a deadline + "
                         "failure classifier; a failing slice's key "
                         "range degrades per --fail-open while every "
                         "other slice keeps serving exactly, with "
                         "half-open probe recovery and (with "
                         "--snapshot-dir) restore-before-rejoin")
    ap.add_argument("--slice-deadline-ms", type=float, default=250.0,
                    help="per-slice sub-dispatch deadline (quarantine "
                         "mode): a slice not resolving within this "
                         "budget is classified failed")
    ap.add_argument("--probe-interval", type=float, default=1.0,
                    help="seconds between half-open probes of a "
                         "quarantined slice")
    ap.add_argument("--quarantine-threshold", type=int, default=1,
                    help="consecutive classified failures before a "
                         "slice quarantines")
    # Chaos harness (ADR-015; TEST/BENCH ONLY — deterministic fault
    # injection in the serving process so loadgen runs can measure
    # degraded-mode serving end to end).
    ap.add_argument("--chaos-scenario", default=None,
                    metavar="NAME",
                    help="arm one chaos scenario in-process (kill-slice, "
                         "slow-slice, wedge-slice, dcn-partition, "
                         "dcn-corrupt, snapshot-stall, migration-stall, "
                         "kill-during-handoff, rejoin-storm). Requires "
                         "--quarantine for the slice scenarios; the "
                         "handoff/rejoin scenarios need --fleet-config. "
                         "Test lever — never set in production")
    ap.add_argument("--chaos-slice", type=int, default=0,
                    help="victim slice index for slice scenarios")
    ap.add_argument("--chaos-after", type=float, default=0.0,
                    help="arm the scenario this many seconds after "
                         "serving starts (0 = immediately) — the "
                         "kill-a-slice-MID-TRAFFIC shape")
    ap.add_argument("--chaos-seconds", type=float, default=0.05,
                    help="delay/stall magnitude for slow-slice / "
                         "snapshot-stall")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="injector RNG seed (failures replay exactly)")
    ap.add_argument("--limit", type=int, default=100)
    ap.add_argument("--window", type=float, default=60.0,
                    help="window seconds")
    ap.add_argument("--fail-open", action="store_true")
    ap.add_argument("--sketch-depth", type=int, default=4)
    ap.add_argument("--sketch-width", type=int, default=65536)
    ap.add_argument("--sub-windows", type=int, default=60)
    ap.add_argument("--dense-capacity", type=int,
                    default=DenseParams.capacity, metavar="N",
                    help="--backend dense: entries of the device-resident "
                         "key directory = the most distinct live keys "
                         "(32 B of device memory a key with the token "
                         "bucket's three columns); a multiple of 128 "
                         "keeps a probe to one vector row. Size it to "
                         "about twice the active keys: rows whose key "
                         "finds no entry are answered by --fail-open. "
                         "With --mesh-devices N it is ONE chip's entries "
                         "(as --sketch-width is a slice's width): about "
                         "twice the active keys / N, since keys spread "
                         "evenly by hash")
    ap.add_argument("--hh-slots", type=int, default=0,
                    help="heavy-hitter side table slots (0 = off; power "
                         "of two >= 16): promoted hot keys get exact "
                         "private counters, and the observatory exports "
                         "them as top-K consumer analytics "
                         "(/healthz consumers, /debug/audit, "
                         "rate_limiter_top_consumer_mass)")
    # Hierarchical cascades + adaptive control (ADR-020).
    ap.add_argument("--tenants", type=int, default=0,
                    help="enable hierarchical cascades (ADR-020): tenant "
                         "capacity (power of two >= 2; 0 = off). Every "
                         "decision then evaluates key -> tenant -> "
                         "global scopes in the same device dispatch; "
                         "tenant ids derive on device from the "
                         "key->tenant map (protocol unchanged)")
    ap.add_argument("--tenant-map", type=int, default=1024,
                    help="key->tenant assignment map capacity (power of "
                         "two)")
    ap.add_argument("--global-limit", type=int, default=0,
                    help="global-scope limit, requests per window across "
                         "ALL keys (0 = unlimited)")
    ap.add_argument("--default-tenant-limit", type=int, default=0,
                    help="per-window limit of the default tenant (every "
                         "unassigned key; 0 = unlimited)")
    ap.add_argument("--tenant", action="append", default=[],
                    metavar="NAME=LIMIT[:WEIGHT[:FLOOR]]",
                    help="register a tenant at boot (repeatable); "
                         "LIMIT 0 = unlimited")
    ap.add_argument("--assign", action="append", default=[],
                    metavar="KEY=TENANT",
                    help="assign a key to a tenant at boot (repeatable)")
    ap.add_argument("--controller", action="store_true",
                    help="run the AIMD adaptive controller (ADR-020): a "
                         "background loop that tightens/relaxes EFFECTIVE "
                         "scope limits off the live observatory signals "
                         "(SLO burn rate, audited false-deny Wilson "
                         "bound, per-tenant in-window mass) between each "
                         "scope's floor and its configured ceiling; "
                         "needs --tenants > 0 (wire --audit for the "
                         "false-deny tighten veto)")
    ap.add_argument("--controller-interval", type=float, default=1.0,
                    help="seconds between AIMD controller ticks")
    # Client-embedded quota leases (ADR-022).
    ap.add_argument("--leases", action="store_true",
                    help="grant client-embedded quota leases (ADR-022): "
                         "clients holding a lease answer allow/allow_n "
                         "for that key from a local token budget at "
                         "memory speed; the budget is debited upfront "
                         "through the normal decide path, so the global "
                         "bound fails toward false-denies, never "
                         "over-admission. Revocations push over the "
                         "granting connection (and gossip to DCN peers); "
                         "the lease TTL bounds a holder that lost the "
                         "push")
    ap.add_argument("--lease-ttl", type=float, default=2.0,
                    help="lease lifetime seconds (renewals extend it); "
                         "ALSO the staleness bound on a partitioned "
                         "holder that missed its revocation push")
    ap.add_argument("--lease-budget", type=int, default=256,
                    help="tokens per grant when the client does not ask "
                         "for a specific amount")
    ap.add_argument("--lease-max", type=int, default=4096,
                    help="active-grant capacity; grants beyond it are "
                         "refused and clients stay on the wire path")
    ap.add_argument("--lease-require-hot", action="store_true",
                    help="only lease keys currently in the heavy-hitter "
                         "side table's top-k (needs --hh-slots): the "
                         "hot-key nomination posture — cold keys stay "
                         "on the wire")
    ap.add_argument("--lease-port", type=int, default=None,
                    help="--native only: serve lease frames on this "
                         "sidecar port (0 = ephemeral, printed in the "
                         "banner). The C++ front door has no lease "
                         "lane; the asyncio door serves lease frames "
                         "on its main port and ignores this flag")
    ap.add_argument("--http-tenants", action="store_true",
                    help="expose tenant management (GET/POST/PUT/DELETE "
                         "/v1/tenants) on the HTTP gateway (OFF by "
                         "default: a quota lever in both directions on "
                         "a curl-able surface)")
    ap.add_argument("--http-tenants-token", default=None,
                    help="bearer token required by /v1/tenants (implies "
                         "--http-tenants); Authorization header only")
    ap.add_argument("--http-migrate-token", default=None,
                    help="enable POST /v1/fleet/migrate (live range "
                         "migration, ADR-018) on the HTTP gateway, gated "
                         "by this bearer token. No token, no endpoint — "
                         "an ownership-move lever is never open")
    ap.add_argument("--http-rebalance-token", default=None,
                    help="enable GET/POST /v1/fleet/rebalance (placement "
                         "brain operator surface, ADR-023: status / "
                         "dry-run / apply / abort) on the HTTP gateway, "
                         "gated by this bearer token. No token, no "
                         "endpoint — same posture as /v1/fleet/migrate")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="micro-batcher flush size: the queue depth that "
                         "dispatches at once AND the most rows one "
                         "dispatch takes. Not given: 4096 for both on the "
                         "asyncio door; the --native door still dispatches "
                         "at once from 4096 queued keys but a dispatch "
                         "takes every whole frame already waiting, up to "
                         "16384 rows (nothing waits for the larger run; "
                         "at most --inflight x 16384 rows are in flight)")
    ap.add_argument("--max-delay-us", type=float, default=200.0,
                    help="micro-batcher coalescing window, microseconds")
    ap.add_argument("--dispatch-timeout-ms", type=float, default=None,
                    help="SLO per dispatch; breach triggers fail-open/closed")
    ap.add_argument("--inflight", type=int, default=8,
                    help="pipelined dispatch window (ADR-010): device "
                         "dispatches kept in flight per shard, overlapping "
                         "host encode/decode with device compute; 1 "
                         "restores the synchronous launch->block path. "
                         "Requires a sketch backend and no "
                         "--dispatch-timeout-ms to take effect")
    ap.add_argument("--native", action="store_true",
                    help="use the C++ epoll front door (native/server.cpp) "
                         "instead of the asyncio server")
    ap.add_argument("--shards", type=int, default=1,
                    help="native front door dispatch shards: keys are "
                         "hash-routed, each shard decides on its own "
                         "limiter concurrently (per-key semantics exact)")
    ap.add_argument("--net-engine", default="auto",
                    choices=("auto", "epoll", "uring"),
                    help="native door wire backend (ADR-026): auto probes "
                         "io_uring at startup and falls back to epoll when "
                         "the kernel or seccomp refuses; epoll forces the "
                         "portable backend; uring requests io_uring but "
                         "still downgrades (recorded in stats/healthz) "
                         "rather than failing")
    ap.add_argument("--io-rings", type=int, default=0,
                    help="native door io ring shards: event-loop threads "
                         "connections are pinned to by accept order; 0 = "
                         "auto (min(4, cores))")
    ap.add_argument("--no-prewarm", action="store_true",
                    help="skip jit pre-warming of batch pad shapes at startup")
    ap.add_argument("--log-level", default="info")
    # Decorator stack (ADR-003 analog; reference docs/ADR/002:170-197 and
    # docs/ADR/003:28-125 plan exactly these wrappers around the limiter).
    ap.add_argument("--circuit-breaker", action="store_true",
                    help="wrap the limiter in CircuitBreakerDecorator "
                         "(trips after --breaker-threshold consecutive "
                         "backend failures; probes after --breaker-cooldown)")
    ap.add_argument("--breaker-threshold", type=int, default=5)
    ap.add_argument("--breaker-cooldown", type=float, default=10.0,
                    help="seconds the breaker stays open before probing")
    ap.add_argument("--log-decisions", action="store_true",
                    help="wrap in LoggingDecorator (decisions at DEBUG, "
                         "fail-open at WARNING)")
    ap.add_argument("--trace", action="store_true",
                    help="wrap in TracingDecorator (jax.profiler "
                         "annotations on every dispatch)")
    # Flight-recorder tracing subsystem (ADR-014).
    ap.add_argument("--flight-recorder", action="store_true",
                    help="turn on the flight recorder (ADR-014): "
                         "per-thread ring buffers of per-stage spans "
                         "stamped on the serving hot path at clock-read "
                         "cost; dump via /debug/trace (needs "
                         "--debug-trace + --http-port) or the "
                         "rate_limiter_stage_seconds histograms on "
                         "/metrics. Off by default = zero overhead")
    ap.add_argument("--flight-recorder-capacity", type=int, default=8192,
                    help="span ring capacity PER THREAD (records; "
                         "rounded up to a power of two). At 32 B/record "
                         "the default is 256 KiB per serving thread")
    ap.add_argument("--debug-trace", action="store_true",
                    help="expose GET /debug/trace (Perfetto/Chrome-trace "
                         "dump of recent spans) and /debug/profile "
                         "(on-demand jax.profiler capture) on the HTTP "
                         "gateway. OFF by default: traces reveal key "
                         "traffic timing — gate like /v1/policy")
    ap.add_argument("--debug-token", default=None,
                    help="bearer token required by the /debug endpoints "
                         "(implies --debug-trace); Authorization header "
                         "only, like every other token")
    # Control-plane event journal (ADR-021).
    ap.add_argument("--no-event-journal", action="store_true",
                    help="disable the control-plane event journal "
                         "(ADR-021). ON by default: controller moves, "
                         "quarantine transitions, handoffs, failovers, "
                         "epoch bumps, and policy/tenant mutations are "
                         "recorded in a bounded in-memory ring (never "
                         "the decide path) and served over bearer-gated "
                         "GET /debug/events")
    ap.add_argument("--event-journal-capacity", type=int, default=4096,
                    help="events held in the journal ring (oldest "
                         "evicted; ~300 B/event)")
    ap.add_argument("--event-journal-dir", default=None, metavar="DIR",
                    help="also spill journal events to append-only "
                         "JSONL segments in DIR (bounded rotation) and "
                         "replay the on-disk tail into the ring at "
                         "startup — a restart keeps the events that "
                         "explain WHY it restarted")
    ap.add_argument("--no-metrics", action="store_true",
                    help="skip the MetricsDecorator (on by default)")
    # Live accuracy observatory (ADR-016).
    ap.add_argument("--audit", action="store_true",
                    help="turn on the live accuracy observatory "
                         "(ADR-016): a deterministic hash-sampled "
                         "fraction of live decisions is mirrored into "
                         "an exact shadow oracle off the hot path; live "
                         "false-deny/false-allow rates with Wilson "
                         "bounds land on /metrics, /healthz, and "
                         "GET /debug/audit, plus the admission-SLO "
                         "burn-rate block. Needs a sketch-family "
                         "backend. Off by default = byte-identical hot "
                         "path")
    ap.add_argument("--audit-sample", type=int, default=64,
                    help="audit 1 in N of the keyspace (hash-coherent: "
                         "a key is always or never audited, so its "
                         "windows stay whole; 1 audits everything)")
    ap.add_argument("--audit-token", default=None,
                    help="bearer token required by GET /debug/audit "
                         "(Authorization header only, like every other "
                         "token; without it the endpoint is open "
                         "whenever --audit is set)")
    ap.add_argument("--audit-twin", action="store_true",
                    help="also run the collision-free CMS twin online, "
                         "separating pure-CMS collision error from "
                         "semantic error in the live stream. COSTS a "
                         "jitted shadow dispatch per audited frame "
                         "(measured ~15-20%% of a CPU box's serving "
                         "throughput — ADR-016 §3), so it is off by "
                         "default; evaluation.evaluate_accuracy always "
                         "runs the split offline")
    ap.add_argument("--log-redact-keys", action="store_true",
                    help="with --log-decisions: log splitmix64 hashes "
                         "instead of raw keys (the PII trust boundary, "
                         "docs/OPERATIONS.md §6)")
    # Cross-pod DCN exchange (parallel/dcn.py over serving/dcn_peer.py).
    ap.add_argument("--dcn-peer", action="append", default=[],
                    metavar="HOST:PORT",
                    help="push completed slabs / debt deltas to this peer "
                         "server (repeatable); both front doors can "
                         "receive (asyncio and --native)")
    ap.add_argument("--dcn-interval", type=float, default=1.0,
                    help="seconds between DCN export+push cycles")
    ap.add_argument("--dcn-listen", action="store_true",
                    help="accept T_DCN_PUSH frames from peers (implied by "
                         "--dcn-peer); off by default so plain deployments "
                         "keep the 1 MiB per-frame bound")
    ap.add_argument("--dcn-max-transfers", type=int, default=4,
                    help="native door: connections allowed to hold a "
                         "DCN-slab-sized receive buffer concurrently "
                         "(size to your peer count; refused peers get a "
                         "typed error and re-push next cycle)")
    # Fleet tier (ADR-017): multi-host scale-out — this server owns a
    # set of keyspace hash buckets; mis-routed rows forward to their
    # owner; peers heartbeat over the DCN channel; a dead peer's ranges
    # fail over to its configured successor.
    ap.add_argument("--fleet-config", default=None, metavar="PATH",
                    help="join a fleet: JSON ownership map (buckets, "
                         "epoch, hosts with id/host/port/ranges/"
                         "successor/snapshot_dir). Implies accepting "
                         "DCN pushes (fleet announces ride that "
                         "channel); needs a sketch-family backend and "
                         "--fleet-self")
    ap.add_argument("--fleet-self", default=None, metavar="ID",
                    help="this server's host id inside --fleet-config")
    ap.add_argument("--fleet-no-forward", action="store_true",
                    help="answer mis-routed frames with the typed "
                         "E_NOT_OWNER redirect instead of proxying them "
                         "to the owner (routing becomes entirely the "
                         "client's job; dumb LBs will see errors)")
    ap.add_argument("--fleet-rejoin", default="auto",
                    choices=["auto", "manual"],
                    help="when a previously-dead peer announces again, "
                         "hand its adopted ranges back automatically "
                         "via the handoff protocol (snapshot -> restore "
                         "on the returning host -> epoch bump; "
                         "ADR-018). 'manual' preserves the ADR-017 "
                         "operator-driven posture")
    ap.add_argument("--fleet-heartbeat", type=float, default=0.5,
                    help="seconds between fleet announce pushes")
    ap.add_argument("--fleet-dead-after", type=float, default=2.0,
                    help="declare a peer dead after this many seconds "
                         "of announce silence (failover trigger)")
    ap.add_argument("--fleet-boot-grace", type=float, default=None,
                    help="seconds from start before a NEVER-seen peer "
                         "can be declared dead (default max(3 x "
                         "dead-after, 15): members prewarming at boot "
                         "are not dead)")
    ap.add_argument("--fleet-forward-deadline", type=float, default=1.0,
                    help="per-call deadline (seconds) on forwarded "
                         "frames; rides the wire so the owner sheds "
                         "expired work (ADR-015)")
    ap.add_argument("--fleet-forward-queue", type=int, default=128,
                    help="bounded per-peer forward queue (outstanding "
                         "fragments); overflow answers per "
                         "fail-open/closed policy")
    ap.add_argument("--fleet-forward-inflight", type=int, default=2,
                    help="pipelined wire frames in flight per forward "
                         "connection (ADR-019: the PR 3 bounded window "
                         "one level up). Small windows coalesce MORE "
                         "rows per wire frame — 2 measured best on "
                         "loopback; raise it on high-RTT links")
    ap.add_argument("--fleet-forward-conns", type=int, default=1,
                    help="pipelined connections per peer; rows pick "
                         "their connection by key hash, so same-key "
                         "send order survives the multi-connection "
                         "link (ADR-019). 1 maximizes window "
                         "occupancy; >1 buys wire parallelism where "
                         "one TCP stream can't fill the NIC")
    ap.add_argument("--fleet-forward-coalesce", type=int, default=16384,
                    help="max rows merged into one coalesced forward "
                         "wire frame (ADR-019; capped at 32768 — the "
                         "coalesced REPLY costs ~24 B/row against the "
                         "1 MiB wire bound)")
    # Load-aware placement (ADR-023): the fleet rebalancing brain.
    ap.add_argument("--rebalance", action="store_true",
                    help="run the placement rebalancer (ADR-023): a "
                         "background loop that merges every member's "
                         "per-bucket decision load, plans bounded "
                         "range moves toward max/mean balance "
                         "(hysteresis + min-residency cooldown, so "
                         "ranges never flap), and executes its OWN "
                         "donated moves through the ADR-018 handoff — "
                         "paced AIMD-style and vetoed by SLO burn / "
                         "false-deny bounds. Needs --fleet-config; "
                         "every member should run it (each executes "
                         "only the moves it donates)")
    ap.add_argument("--rebalance-interval", type=float, default=10.0,
                    help="seconds between rebalance planning cycles "
                         "(vetoes and failed moves back the effective "
                         "interval off multiplicatively)")
    ap.add_argument("--rebalance-max-moves", type=int, default=2,
                    help="range moves budgeted per planning cycle")
    ap.add_argument("--rebalance-trigger", type=float, default=1.4,
                    help="plan only when fleet max/mean decision-load "
                         "imbalance reaches this ratio (hysteresis "
                         "upper band)")
    ap.add_argument("--rebalance-target", type=float, default=1.15,
                    help="plan down toward this imbalance ratio "
                         "(hysteresis lower band; must be below the "
                         "trigger or the fleet flaps)")
    ap.add_argument("--rebalance-min-residency", type=float,
                    default=60.0,
                    help="seconds a moved bucket is frozen before it "
                         "may move again (flap prevention)")
    ap.add_argument("--rebalance-seed", type=int, default=0,
                    help="planner seed, salted into every plan id "
                         "(plans are deterministic: same load view -> "
                         "same plan)")
    ap.add_argument("--dcn-secret", default=None,
                    help="shared secret HMAC-gating T_DCN_PUSH frames "
                         "(both sides must set it; prefer the "
                         "RATELIMITER_TPU_DCN_SECRET env var to keep it "
                         "off argv). Without it, anyone with reach to the "
                         "serving port can inject counter mass — firewall "
                         "the port or set a secret (docs/OPERATIONS.md)")
    ap.add_argument("--http-port", type=int, default=None,
                    help="also serve the HTTP gateway (429 + X-RateLimit-* "
                         "headers, /healthz, /metrics) on this port; HTTP "
                         "decisions share the micro-batcher with binary "
                         "traffic on the asyncio front door, and are "
                         "shard-routed on the native one")
    ap.add_argument("--http-reset", action="store_true",
                    help="expose POST /v1/reset on the HTTP gateway "
                         "(OFF by default: reset is a quota-erase lever "
                         "on a curl-able surface)")
    ap.add_argument("--http-reset-token", default=None,
                    help="bearer token required by /v1/reset (implies "
                         "--http-reset); Authorization header only — "
                         "query-string tokens are never accepted")
    ap.add_argument("--http-policy", action="store_true",
                    help="expose the tiered-override endpoint "
                         "(GET/POST/PUT/DELETE /v1/policy) on the HTTP "
                         "gateway (OFF by default: overrides are a "
                         "quota-GRANT lever on a curl-able surface)")
    ap.add_argument("--http-policy-token", default=None,
                    help="bearer token required by /v1/policy (implies "
                         "--http-policy); Authorization header only")
    # Durability subsystem (ratelimiter_tpu/persistence/, ADR-009).
    ap.add_argument("--snapshot-dir", default=None,
                    help="enable the durability subsystem: write-ahead "
                         "log for mutations (policy/reset/config) plus "
                         "async background snapshots in this directory; "
                         "on start, state recovers from the newest "
                         "snapshot + WAL replay. Off by default")
    ap.add_argument("--snapshot-interval", type=float, default=30.0,
                    help="seconds between background snapshots (bounds "
                         "the decisions lost to kill -9 at one "
                         "interval of traffic, in the under-counting "
                         "direction)")
    ap.add_argument("--snapshot-after-mutations", type=int, default=0,
                    help="also snapshot after this many WAL mutations "
                         "(0 = interval only)")
    ap.add_argument("--snapshot-retain", type=int, default=3,
                    help="snapshots kept on disk; older ones and their "
                         "WAL prefix are pruned")
    ap.add_argument("--wal-fsync", default="always",
                    choices=["always", "interval", "never"],
                    help="WAL durability: fsync every mutation (default; "
                         "mutations are rare control-plane ops), at most "
                         "every 50ms, or never (OS flushing only)")
    ap.add_argument("--http-snapshot-token", default=None,
                    help="bearer token required by POST /v1/snapshot on "
                         "the HTTP gateway (the trigger is wired "
                         "whenever --snapshot-dir is set; without a "
                         "token it is open — snapshots cost disk churn, "
                         "so gate it on shared surfaces). Authorization "
                         "header only")
    ap.add_argument("--grpc-port", type=int, default=None,
                    help="also serve the gRPC contract "
                         "(api/proto/ratelimiter.proto) on this port; "
                         "needs the optional grpcio runtime + protoc. "
                         "Decisions share the limiter (and shard router "
                         "under --native) with all other surfaces")
    return ap


def build_limiter_stack(limiter, args, shard: int = 0):
    """Apply the configured decorator stack, innermost first.

    Order (inner -> outer): Tracing (annotates the real device dispatch),
    CircuitBreaker (judges backend health from real calls), Metrics
    (observes everything, including breaker short-circuits), Logging
    (outermost, sees final outcomes). ``shard`` labels the accuracy-
    envelope gauges so dispatch shards report distinct series."""
    if args.trace:
        limiter = TracingDecorator(limiter)
    if args.circuit_breaker:
        limiter = CircuitBreakerDecorator(
            limiter, failure_threshold=args.breaker_threshold,
            cooldown=args.breaker_cooldown)
    if not args.no_metrics:
        limiter = MetricsDecorator(limiter, shard=str(shard))
    if args.log_decisions:
        limiter = LoggingDecorator(
            limiter, redact_keys=getattr(args, "log_redact_keys", False))
    return limiter


def _envelope_health(limiters) -> dict:
    """Accuracy-envelope fields for /healthz (windowed sketch only): a
    growing overload_periods flags an undersized geometry at the
    operational surface, not just in logs (VERDICT r4 weak 6). With
    dispatch shards, pass EVERY shard limiter: counters/mass sum across
    shards (each shard has its own budget, so the aggregate budget is
    per-shard x N) and ``shards_overloaded`` says how many are currently
    past their own budget. A sliced mesh limiter expands to its
    per-device slices (same aggregation, one series per device)."""
    from ratelimiter_tpu.observability.decorators import undecorated

    lims = [undecorated(lim) for lim in limiters]
    lims = [sl for lim in lims for sl in lim.sub_limiters()]
    lims = [lim for lim in lims if hasattr(lim, "_period_mass")]
    if not lims:
        return {}
    masses = [lim.in_window_admitted_mass() for lim in lims]
    return {"overload_periods": sum(lim.overload_periods for lim in lims),
            "in_window_admitted_mass": sum(masses),
            "mass_budget": sum(lim.mass_budget for lim in lims),
            "shards_overloaded": sum(
                mass > lim.mass_budget
                for lim, mass in zip(lims, masses)),
            "overload_policy": lims[0].config.sketch.overload_policy}


def _debt_slab_health(limiters) -> dict:
    """Debt-slab occupancy/collision fields for /healthz (token-bucket
    sketch only) — the continuous-decay mirror of `_envelope_health`
    (ROADMAP item 5: strict gating doesn't transfer to the debt slab,
    visibility does). Aggregation across dispatch shards / mesh slices:
    occupancy and collision_p report the WORST unit (a hot slice hides
    behind healthy ones under a mean), cell counts sum. Each call costs
    one device fetch per unit — /healthz cadence, never the decide
    path."""
    from ratelimiter_tpu.observability.decorators import undecorated

    lims = [undecorated(lim) for lim in limiters]
    lims = [sl for lim in lims for sl in lim.sub_limiters()]
    lims = [lim for lim in lims if hasattr(lim, "debt_slab_stats")]
    if not lims:
        return {}
    stats = [lim.debt_slab_stats() for lim in lims]
    return {"debt_slab": {
        "occupancy": max(s["occupancy"] for s in stats),
        "collision_p": max(s["collision_p"] for s in stats),
        "nonzero_cells": sum(s["nonzero_cells"] for s in stats),
        "cells": sum(s["cells"] for s in stats),
        "units": len(stats)}}


def _consumers_health(limiters, k: int = 10) -> dict:
    """Top-K consumer block for /healthz (heavy-hitter side table,
    ADR-016 §5): per-unit consumer_stats merged across dispatch shards /
    mesh slices — a consumer lives on exactly one slice (keys
    hash-route), so the merged ranking is a straight sort over the
    union. Consumer identities are hash tokens, never raw keys
    (OPERATIONS §6). Empty when no unit runs an hh table."""
    from ratelimiter_tpu.observability.decorators import undecorated

    lims = [undecorated(lim) for lim in limiters]
    lims = [sl for lim in lims for sl in lim.sub_limiters()]
    units = [(i, lim) for i, lim in enumerate(lims)
             if getattr(lim, "has_hh", False)]
    if not units:
        return {}
    rows = []
    occupied = slots = mass = 0
    for i, lim in units:
        st = lim.consumer_stats(k=k)
        slots += st["slots"]
        occupied += st["occupied"]
        mass += st.get("tracked_mass", 0)
        for row in st["top"]:
            rows.append({**row, "slice": i})
    rows.sort(key=lambda r: -r["in_window"])
    return {"consumers": {
        "slots": slots,
        "occupied": occupied,
        "tracked_mass": mass,
        "top": rows[:k]}}


def _audit_health() -> dict:
    """Audit envelope for /healthz: the observatory's headline numbers
    (rates + confidence + drop counters); the full per-slice breakdown
    lives on GET /debug/audit."""
    from ratelimiter_tpu.observability import audit

    aud = audit.AUDITOR
    if aud is None:
        return {}
    st = aud.status()
    return {"audit": {
        "sample": st["sample"],
        "samples": st["samples"],
        "false_deny_rate": st["false_deny_rate"],
        "false_deny_wilson95": st["false_deny_wilson95"],
        "false_allow_rate": st["false_allow_rate"],
        # Raw tallies — the MERGEABLE form (ADR-021): the fleet rollup
        # sums these across members and recomputes rates + Wilson over
        # the merged counts (fleet/tower.merge_audit).
        "false_denies": st["false_denies"],
        "false_allows": st["false_allows"],
        "oracle_allows": st["oracle_allows"],
        "fail_open_samples": st["fail_open_samples"],
        "dropped_decisions": st["dropped_decisions"],
        "oracle_errors": st["oracle_errors"]}}


def _slo_health(slo) -> dict:
    return {"slo": slo.status()} if slo is not None else {}


def _events_health() -> dict:
    from ratelimiter_tpu.observability import events as events_mod

    j = events_mod.JOURNAL
    return {"events": j.status()} if j is not None else {}


def _make_member_info(args, fleet_core):
    """Member identity (ADR-021 satellite): the dict mirrored into
    /healthz AND exported as the ``rate_limiter_member_info`` identity
    gauge, so rolled-up series and stitched traces are attributable to
    a member (who am I, which map epoch am I serving, which door/ABI,
    which backend)."""
    abi = "py"
    if args.native:
        from ratelimiter_tpu.serving.native_server import _ABI

        abi = str(_ABI)

    def info() -> dict:
        return {
            "self": args.fleet_self or f"{args.host}:{args.port}",
            "backend": args.backend,
            "algorithm": args.algorithm,
            "door": "native" if args.native else "asyncio",
            "abi": abi,
            "fleet_epoch": (int(fleet_core.map.epoch)
                            if fleet_core is not None else None),
        }

    g_info = obs_metrics.DEFAULT.gauge(
        "rate_limiter_member_info",
        "Identity gauge (value always 1): fleet self id, current "
        "ownership-map epoch, serving door + native ABI, and backend "
        "kind as labels — joins rolled-up series and stitched traces "
        "to a member (ADR-021)")

    def collect() -> None:
        # clear-then-set: the epoch LABEL changes over time, and a
        # gauge only overwrites label sets it is told about — stale
        # identities would otherwise persist across failovers. The
        # member id renders under the label "id" ("self" cannot ride
        # a **labels kwarg — it collides with the bound method).
        g_info.clear()
        d = info()
        g_info.set(1.0, **{("id" if k == "self" else k):
                           ("-" if v is None else str(v))
                           for k, v in d.items()})

    obs_metrics.DEFAULT.add_collect_hook(collect)
    return info


def _hierarchy_health(hier, controller) -> dict:
    """Cascade block for /healthz (ADR-020): per-scope in-window mass +
    effective/ceiling limits (summed across dispatch units by the
    fanout), plus the AIMD controller's move counters when it runs."""
    if hier is None:
        return {}
    st = hier.hierarchy_stats()
    if controller is not None:
        st["controller"] = {"ticks": controller.ticks,
                            "tightened": controller.tightened,
                            "relaxed": controller.relaxed,
                            "interval": controller.interval}
    return {"hierarchy": st}


def _boot_tenants(hier, args) -> None:
    """Apply --tenant NAME=LIMIT[:WEIGHT[:FLOOR]] and --assign
    KEY=TENANT boot flags (after recovery, so operator flags win over a
    snapshot's registry for the names they touch)."""
    for spec in args.tenant:
        name, _, rest = spec.partition("=")
        if not name or not rest:
            raise SystemExit(f"bad --tenant {spec!r}; expected "
                             f"NAME=LIMIT[:WEIGHT[:FLOOR]]")
        parts = rest.split(":")
        try:
            limit = int(parts[0]) or None
            weight = int(parts[1]) if len(parts) > 1 and parts[1] else 1
            floor = (int(parts[2])
                     if len(parts) > 2 and parts[2] else None)
        except ValueError:
            raise SystemExit(f"bad --tenant {spec!r}; expected "
                             f"NAME=LIMIT[:WEIGHT[:FLOOR]]") from None
        hier.set_tenant(name, limit, weight=weight, floor=floor)
    for spec in args.assign:
        key, _, tenant = spec.partition("=")
        if not key or not tenant:
            raise SystemExit(f"bad --assign {spec!r}; expected "
                             f"KEY=TENANT")
        hier.assign_tenant(key, tenant)


def _setup_hierarchy(args, cfg, units, *, slo_tracker, auditor,
                     fleet_membership):
    """Mount the cascade's management surface over the door's dispatch
    units and (optionally) start the AIMD controller over it. Returns
    ``(hier, controller)`` — (None, None) when the hierarchy is off."""
    if not cfg.hierarchy.enabled:
        return None, None
    from ratelimiter_tpu.hierarchy import AIMDController, HierarchyFanout

    hier = HierarchyFanout(list(units))
    _boot_tenants(hier, args)
    if fleet_membership is not None:
        # Effective limits gossip on every announce; members adopt the
        # newest revision (last-writer-wins) so the fleet converges on
        # whichever member's controller moved last.
        fleet_membership.hier_payload_fn = hier.hierarchy_payload
        fleet_membership.hier_apply_fn = hier.apply_hierarchy_payload
    controller = None
    if args.controller:
        controller = AIMDController(
            hier,
            slo_status=(slo_tracker.status if slo_tracker is not None
                        else None),
            audit_status=(auditor.status if auditor is not None
                          else None),
            interval=args.controller_interval,
            publish=((lambda _payload: fleet_membership.announce_once())
                     if fleet_membership is not None else None),
            registry=obs_metrics.DEFAULT)
    return hier, controller


def _make_fleet_migrate(args, fleet_core, fleet_membership):
    """POST /v1/fleet/migrate hook (ADR-018 operator surface): bound to
    migrate_ranges, reporting the post-move epoch. None unless this is a
    fleet member AND an operator token is set."""
    if fleet_membership is None or not args.http_migrate_token:
        return None

    def migrate(ranges, to, wait):
        ok = fleet_membership.migrate_ranges(ranges, to, wait=wait)
        return {"ok": bool(ok), "epoch": int(fleet_core.map.epoch),
                "to": to, "ranges": [list(r) for r in ranges]}

    return migrate


def make_threadsafe_decide(batcher, loop):
    """Single-decision bridge from gateway/gRPC worker threads into the
    event loop's micro-batcher: every surface shares device dispatches.
    Trace-aware (ADR-014): a sampled HTTP/gRPC request's trace id rides
    into the batcher so its coalesced dispatch records under it.
    Deadline-aware (ADR-015): a caller's RELATIVE budget anchors to the
    local monotonic clock and the batcher sheds the work per policy if
    it expires in the coalescing queue."""
    def decide(key: str, n: int, trace_id: int = 0, deadline=None):
        abs_deadline = (time.monotonic() + float(deadline)
                        if deadline is not None else 0.0)
        return asyncio.run_coroutine_threadsafe(
            batcher.submit(key, n, trace_id=trace_id,
                           deadline=abs_deadline),
            loop).result(timeout=30)

    return decide


def make_threadsafe_decide_many(batcher, loop):
    """Bulk bridge for gRPC AllowBatch: the WHOLE frame is submitted to
    the micro-batcher before any result is awaited, so N items coalesce
    into O(1) batched dispatches (they typically land in ONE, together
    with concurrent binary-protocol traffic) instead of N sequential
    submit-wait round-trips. Results return in request order
    (submit_many_nowait preserves it; gather keeps positions)."""
    def decide_many(pairs):
        async def _run():
            futs = batcher.submit_many_nowait(pairs)
            return await asyncio.gather(*futs)

        return asyncio.run_coroutine_threadsafe(
            _run(), loop).result(timeout=30)

    return decide_many


def _setup_leases(args, *, limiter, decide, fleet_core, pushers, persist):
    """Lease authority (ADR-022): grants/renewals/returns plus the
    revocation fan-out. Debits ride ``decide`` — the door's shared
    dispatch path, so a lease budget is charged exactly like a wire
    decision (and lands on the owning shard/peer). Revocations gossip
    over the DCN pushers when the deployment runs them, and the grant
    table rides the snapshot cycle as a checkpoint sidecar."""
    if not args.leases:
        return None
    from ratelimiter_tpu.leases import LeaseManager
    from ratelimiter_tpu.observability.decorators import undecorated

    epoch_fn = None
    owns_fn = None
    if fleet_core is not None:
        epoch_fn = lambda: int(fleet_core.map.epoch)  # noqa: E731

        def owns_fn(key: str) -> bool:
            h = fleet_core.hash_keys([key])
            return bool(fleet_core.all_local(
                fleet_core.owners_of_hash(h)))

    mgr = LeaseManager(
        undecorated(limiter), decide=decide,
        ttl=args.lease_ttl, default_budget=args.lease_budget,
        max_leases=args.lease_max,
        require_hot=args.lease_require_hot,
        epoch_fn=epoch_fn, owns_fn=owns_fn,
        gossip=(pushers[0].push_lease if pushers else None),
        registry=obs_metrics.DEFAULT)
    if persist is not None:
        persist.add_sidecar("leases", mgr)
        if persist.restore_sidecar("leases", mgr):
            logging.getLogger("ratelimiter_tpu.leases").info(
                "lease table restored from snapshot sidecar "
                "(restored grants are tombstone-only: their mass "
                "stays charged, holders re-grant)")
    return mgr


def _lease_guarded_policy(lease_mgr, set_fn, delete_fn):
    """Wrap a door's policy callables so an override mutation revokes
    the key's outstanding leases — a holder must not keep answering
    locally under the limit the operator just changed. The wrappers
    preserve the wrapped callables' signatures (gateway and gRPC both
    call them)."""
    if lease_mgr is None:
        return set_fn, delete_fn
    from ratelimiter_tpu.serving import protocol as p

    def set_(key, limit=None, **kw):
        ov = set_fn(key, limit, **kw)
        lease_mgr.revoke_key(key, p.LEASE_REV_POLICY)
        return ov

    def delete_(key):
        existed = delete_fn(key)
        if existed:
            lease_mgr.revoke_key(key, p.LEASE_REV_POLICY)
        return existed

    return set_, delete_


def _lease_guarded_reset(lease_mgr, reset_fn):
    """Reset erases the window counter holding a grant's debited mass,
    so leased tokens spent afterwards would be invisible to the bound —
    revoke the key's leases alongside (same rule as the binary door's
    T_RESET path)."""
    if lease_mgr is None:
        return reset_fn
    from ratelimiter_tpu.serving import protocol as p

    def reset_(key):
        out = reset_fn(key)
        lease_mgr.revoke_key(key, p.LEASE_REV_MANUAL)
        return out

    return reset_


def _lease_controller_hook(lease_mgr):
    """AIMD tighten → lease revocation (ADR-022): any tightened scope
    invalidates outstanding budgets sized under the old effective
    limits. Scope→keys is not tracked, so the hook revokes ALL grants —
    coarse, but in the safe direction (lease churn, never
    over-admission)."""
    if lease_mgr is None:
        return None
    from ratelimiter_tpu.serving import protocol as p

    return lambda _scope: lease_mgr.revoke_all(p.LEASE_REV_CONTROLLER)


def _lease_health(lease_mgr) -> dict:
    return {"leases": lease_mgr.status()} if lease_mgr is not None else {}


def _prewarm(limiters, max_batch: int) -> None:
    """Compile every batch pad shape the serving tier can produce BEFORE
    accepting traffic, so no client request ever pays a jit compile.
    ``limiters`` is one limiter or the list of a door's dispatch units;
    ``max_batch`` is the most rows a dispatch takes: the door's drain
    cap (native_server.batch_rule). Warmed are the powers of two up to
    it, PLUS one shape past it — the native door's coalescer cuts runs
    at max_batch (and segments hashed frames across the boundary,
    ADR-013), but a single wire frame larger than max_batch still
    dispatches alone and pads to the next shape. (The
    r06 mixed-traffic collapse was exactly this: ragged coalesced runs
    overshooting max_batch by a slice landed multi-second XLA compiles
    on the hot path.) With the persistent compilation cache this is fast
    on every start after the first. Every device slice is warmed across
    the full shape range (a skewed frame can hand any slice up to the
    whole batch, so partial per-slice warming would leave compiles on
    the hot path), the slices SIDE BY SIDE, a thread a slice: a device's
    programs are compiled for that device, XLA compiles with the GIL
    released, and four 2 GB tables warmed one after another would not
    start inside the time a first run is allowed."""
    from concurrent.futures import ThreadPoolExecutor

    from ratelimiter_tpu.observability.decorators import undecorated

    t0 = time.time()
    if not isinstance(limiters, (list, tuple)):
        limiters = [limiters]
    top = 2 * max_batch
    targets = [tgt for lim in limiters
               for tgt in undecorated(lim).sub_limiters()]
    if len(targets) == 1:
        _prewarm_slice(targets[0], 0, top)
    else:
        with ThreadPoolExecutor(len(targets),
                                thread_name_prefix="prewarm") as pool:
            # list(): a slice's failure is the start's.
            list(pool.map(lambda it: _prewarm_slice(it[1], it[0], top),
                          enumerate(targets)))
    for lim in limiters:
        und = undecorated(lim)
        if hasattr(und, "prewarm_routed"):
            # Collective router (ADR-024): the shard_map'd all_to_all
            # step is its own compilation per pad shape, distinct from
            # the per-slice kernels warmed above (those stay warm for
            # the overflow/strict fallback path).
            und.prewarm_routed(max_batch)
    logging.getLogger("ratelimiter_tpu.serving").info(
        "prewarmed pad shapes up to %d (%d dispatch target%s) in %.1fs",
        top, len(targets), "s" if len(targets) != 1 else "",
        time.time() - t0)


def _prewarm_slice(tgt, index: int, top: int) -> None:
    """One dispatch target's share of ``_prewarm``, under a ``prewarm``
    span that names the slice: a capture of a start (or the ring) shows
    whether the slices did compile side by side. A pad shape is warmed
    by deciding it once on both lanes."""
    import numpy as np

    from ratelimiter_tpu.observability import tracing
    from ratelimiter_tpu.observability.decorators import undecorated

    # The dense backend's directory holds a key per id it has seen.
    keyed = hasattr(undecorated(tgt), "directory_stats")
    with tracing.span("prewarm", shard=index, batch=top):
        size = 8
        while True:
            size = min(size, top)
            h = np.arange(size, dtype=np.uint64) + (1 << 62)
            if keyed:
                # A compile needs the shape, not distinct keys: eight
                # made-up ids a lane, whatever the directory's capacity.
                h = (h & np.uint64(7)) + np.uint64(1 << 62)
            tgt.allow_hashed(h, now=0.0)
            if hasattr(undecorated(tgt), "allow_ids"):
                # The hashed wire lane's premix step (splitmix64 in-jit,
                # ADR-011) is a distinct compilation per shape — warm it
                # too so the first ALLOW_HASHED frame never pays a
                # compile.
                tgt.allow_ids(h, now=0.0)
            if size >= top:
                break
            size *= 2
        if keyed:
            # Give up the made-up ids above (sent at now=0, so idle for
            # good) before the server serves.
            tgt.prune()


def _configure_jax(args) -> None:
    """x64 + the persistent compile cache (core/jaxcfg.py), BEFORE any
    JAX backend initializes. The exact backend never imports JAX, so
    skip entirely there to keep its startup instant."""
    if args.backend == "exact":
        return
    from ratelimiter_tpu.core import jaxcfg

    jaxcfg.configure()


def _device_report(args, limiters) -> str:
    """The ``device=`` field of both banners and the startup log line:
    platform, device_kind and device count as JAX reports them in THIS
    process, the (constant) kernel path, and the device ids each
    dispatch unit's state sits on — read off the arrays after
    prewarm, not off what was asked for. It is how a caller (and
    chip_smoke.py) tells which device answers."""
    if args.backend == "exact":
        return "device=host"
    import jax

    from ratelimiter_tpu.observability.decorators import undecorated

    units = [undecorated(u) for lim in limiters
             for u in undecorated(lim).sub_limiters()]
    placed = ["+".join(str(i) for i in sorted(
        {d.id for leaf in u._state.values() for d in leaf.devices()}))
        for u in units]
    devs = jax.devices()
    # ``kernels=jnp`` is a constant since PR 30 (one table-access path,
    # chosen in ops/sortmerge._use_sortmerge): the word stays because
    # chipbench/runner.py's _BANNER and chip_smoke.py's regex both
    # require ``kernels=\w+`` and fail a run whose banner lacks it
    # (ROADMAP D10).
    report = (f"device={devs[0].platform}/{devs[0].device_kind} "
              f"x{len(devs)} kernels=jnp "
              f"slice_devices={','.join(placed)}")
    if args.backend == "dense":
        report += f" dense_capacity={args.dense_capacity}"
    log = logging.getLogger("ratelimiter_tpu.serving")
    if devs[0].platform == "cpu" and not os.environ.get("JAX_PLATFORMS"):
        # JAX found no accelerator and fell back on its own: say so
        # loudly — nobody asked for the CPU.
        log.warning("no accelerator found, serving from the CPU: %s",
                    report)
    else:
        log.info("%s", report)
    return report


async def amain(args) -> None:
    logging.basicConfig(level=args.log_level.upper())
    _configure_jax(args)
    from ratelimiter_tpu import HierarchySpec, MeshSpec, PersistenceSpec
    from ratelimiter_tpu.observability import tracing
    from ratelimiter_tpu.serving.native_server import batch_rule

    if args.flight_recorder:
        # Before any serving thread starts; the registry hookup derives
        # rate_limiter_stage_seconds at scrape time (ADR-014).
        tracing.enable(args.flight_recorder_capacity,
                       registry=obs_metrics.DEFAULT)
    if not args.no_event_journal:
        # Control-plane event journal (ADR-021): ON by default — events
        # are rare (never the decide path) and the whole point is
        # reconstructing incidents nobody predicted. Enabled before any
        # subsystem that emits (controller, quarantine, membership).
        from ratelimiter_tpu.observability import events as events_mod

        events_mod.enable(args.event_journal_capacity,
                          host=(args.fleet_self or
                                f"{args.host}:{args.port}"),
                          registry=obs_metrics.DEFAULT,
                          spill_dir=args.event_journal_dir)
    http_debug = bool(args.debug_trace or args.debug_token)

    cfg = Config(
        algorithm=Algorithm(args.algorithm),
        limit=args.limit,
        window=args.window,
        fail_open=args.fail_open,
        sketch=SketchParams(depth=args.sketch_depth, width=args.sketch_width,
                            sub_windows=args.sub_windows,
                            hh_slots=args.hh_slots),
        dense=DenseParams(capacity=args.dense_capacity),
        persistence=PersistenceSpec(
            dir=args.snapshot_dir,
            snapshot_interval=args.snapshot_interval,
            snapshot_after_mutations=args.snapshot_after_mutations,
            retain=args.snapshot_retain,
            wal_fsync=args.wal_fsync),
        mesh=MeshSpec(devices=args.mesh_devices,
                      router=args.router,
                      bin_headroom=args.bin_headroom,
                      quarantine=args.quarantine,
                      slice_deadline=args.slice_deadline_ms * 1e-3,
                      probe_interval=args.probe_interval,
                      failure_threshold=args.quarantine_threshold),
        hierarchy=HierarchySpec(tenants=args.tenants,
                                map_capacity=args.tenant_map,
                                global_limit=args.global_limit,
                                default_tenant_limit=args.
                                default_tenant_limit),
    )
    if cfg.hierarchy.enabled and args.backend not in ("sketch", "mesh"):
        raise SystemExit("--tenants needs a sketch-family backend "
                         "(--backend sketch or --backend mesh)")
    if args.controller and not cfg.hierarchy.enabled:
        raise SystemExit("--controller needs --tenants > 0")
    if (args.tenant or args.assign) and not cfg.hierarchy.enabled:
        raise SystemExit("--tenant/--assign need --tenants > 0")
    if args.mesh_devices is not None and args.backend not in ("mesh",
                                                              "dense"):
        raise SystemExit("--mesh-devices needs --backend mesh (sketch "
                         "slices) or --backend dense (exact slices)")
    # --backend dense over --mesh-devices chips: exact slices mounted as
    # --backend mesh mounts sketch ones, behind the host router.
    dense_mesh = args.backend == "dense" and args.mesh_devices is not None
    sliced = args.backend == "mesh" or dense_mesh
    if dense_mesh and args.router == "collective":
        raise SystemExit(
            "--router collective cannot carry --backend dense: the routed "
            "step neither donates its state nor may select between an old "
            "and a new table-sized leaf (ops/route_kernels.py), so every "
            "frame would copy each chip's table. Leave --router at host")
    if dense_mesh and args.snapshot_dir:
        raise SystemExit(
            "--snapshot-dir is not supported with --backend dense "
            "--mesh-devices: an exact slice's snapshot cannot be "
            "re-bucketed onto another slice count and the deployment "
            "claims no durability; run one table (no --mesh-devices) "
            "to snapshot it")
    if args.rebalance and not args.fleet_config:
        raise SystemExit("--rebalance needs --fleet-config (the "
                         "placement brain moves fleet ranges)")
    if args.rebalance and args.rebalance_target >= args.rebalance_trigger:
        raise SystemExit("--rebalance-target must be below "
                         "--rebalance-trigger (the hysteresis band "
                         "prevents flapping)")
    if args.lease_require_hot and not args.leases:
        raise SystemExit("--lease-require-hot needs --leases")
    if args.lease_require_hot and args.hh_slots <= 0:
        raise SystemExit("--lease-require-hot needs --hh-slots > 0 "
                         "(hot-key nomination reads the heavy-hitter "
                         "side table)")
    if args.lease_port is not None and not args.native:
        raise SystemExit("--lease-port is the native door's lease "
                         "sidecar; the asyncio door serves lease "
                         "frames on its main port")
    if args.quarantine and args.backend != "mesh":
        raise SystemExit("--quarantine needs --backend mesh (failure "
                         "domains are per device slice)")
    if args.router != "host" and args.backend != "mesh":
        raise SystemExit("--router needs --backend mesh (it selects how "
                         "mixed frames reach the device slices)")
    if args.router == "collective" and args.quarantine:
        raise SystemExit(
            "--router collective is incompatible with --quarantine: a "
            "collective dispatch is ONE mesh-wide shard_map execution, "
            "so a single slice's fault has whole-mesh blast radius and "
            "per-slice failure domains cannot contain it (ADR-024). "
            "Use --router host for quarantined deployments.")
    start_chaos = None
    if args.chaos_scenario:
        slice_scen = args.chaos_scenario in ("kill-slice", "slow-slice",
                                             "wedge-slice")
        if slice_scen and not args.quarantine:
            raise SystemExit("--chaos-scenario slice faults need "
                             "--quarantine (otherwise nothing contains "
                             "them)")
        from ratelimiter_tpu import chaos as chaos_pkg

        _inj = chaos_pkg.install(seed=args.chaos_seed)

        def _arm_chaos() -> None:
            chaos_pkg.scenario(args.chaos_scenario, _inj,
                               slice_idx=args.chaos_slice,
                               seconds=args.chaos_seconds)
            logging.getLogger("ratelimiter_tpu.serving").warning(
                "chaos scenario %s armed (slice %d, seed %d)",
                args.chaos_scenario, args.chaos_slice, args.chaos_seed)

        def start_chaos() -> None:
            # Called once SERVING starts (the banner), not at parse
            # time: --chaos-after counts from when traffic can flow, so
            # prewarm/compile time never eats the delay (the
            # kill-a-slice-MID-TRAFFIC shape needs a clean pre-fault
            # phase).
            if args.chaos_after > 0:
                import threading

                t = threading.Timer(args.chaos_after, _arm_chaos)
                # Daemon: a server stopped before the delay elapses must
                # exit promptly, not join a timer waiting to arm chaos
                # against a torn-down limiter.
                t.daemon = True
                t.start()
            else:
                _arm_chaos()
    if sliced and args.shards > 1:
        raise SystemExit(f"--backend {args.backend} over device slices "
                         "routes one dispatch shard per device; use "
                         "--mesh-devices, not --shards")
    persist = None
    if cfg.persistence.enabled:
        from ratelimiter_tpu.persistence import PersistenceManager

        persist = PersistenceManager(cfg.persistence)

    def decorate(lim, shard: int = 0):
        lim = build_limiter_stack(lim, args, shard=shard)
        # Outermost wrapper: every surface's mutations reach the WAL.
        return persist.wrap(lim) if persist is not None else lim

    # --backend mesh behind the NATIVE door mounts the device-pinned
    # slices directly as the C++ door's dispatch shards (one shard ==
    # one device): the FNV/splitmix shard router becomes the
    # shard→device router and each device runs its own pipelined
    # launch/resolve chain, collective-free (ADR-012). The asyncio door
    # serves the composite SlicedMeshLimiter instead — the micro-batcher
    # pipelines whole frames and the limiter fans each frame out to its
    # owning devices. --router collective (ADR-024) keeps the composite
    # shape under BOTH doors: the whole mesh is one dispatch shard and
    # each frame is one shard_map'd SPMD step, so mounting per-device
    # shards would defeat the point.
    mesh_native = bool(sliced and args.native
                       and args.router != "collective")
    slices = None
    qmgr = None
    if mesh_native:
        from ratelimiter_tpu.parallel.limiter import build_slices

        slices = build_slices(
            cfg, backend="dense" if dense_mesh else "sketch")
        if cfg.mesh.quarantine:
            # Native door failure domains (ADR-015): one guard per
            # mounted shard — the C++ shard router IS the slice router,
            # so a guard around each shard limiter scopes faults to
            # exactly one key range.
            from ratelimiter_tpu.parallel.quarantine import (
                QuarantineManager,
                SliceGuard,
            )

            qmgr = QuarantineManager(
                len(slices), clock=slices[0].clock,
                probe_interval=cfg.mesh.probe_interval,
                failure_threshold=cfg.mesh.failure_threshold)
            slices = [SliceGuard(s, i, qmgr,
                                 deadline=cfg.mesh.slice_deadline)
                      for i, s in enumerate(slices)]
        limiter = decorate(slices[0])
    else:
        lim_kw = {}
        if (cfg.hierarchy.enabled and args.native and args.shards > 1
                and args.backend == "sketch"):
            # Multi-shard native door (ADR-020): each dispatch shard
            # enforces its equal share of every tenant/global limit
            # (keys hash-route, shards share no counters); the clone
            # shards inherit the divisor in native_server.
            lim_kw["hier_divisor"] = args.shards
        limiter = decorate(create_limiter(cfg, backend=args.backend,
                                          **lim_kw))
        if args.backend == "mesh":
            from ratelimiter_tpu.observability.decorators import undecorated

            qmgr = getattr(undecorated(limiter), "quarantine", None)
    # The coalescer's two numbers: the queue depth that dispatches at
    # once, and the most rows a dispatch takes (what prewarm must cover).
    # Only the native door's default tells them apart.
    wait_rows, drain_rows = batch_rule(
        args.max_batch, native=args.native,
        slo=bool(args.dispatch_timeout_ms))
    if args.backend != "exact" and not args.no_prewarm:
        _prewarm([limiter] + (slices[1:] if slices is not None else []),
                 drain_rows)
    device_report = _device_report(
        args, slices if slices is not None else [limiter])
    # Live accuracy observatory (ADR-016): shadow-oracle auditor + SLO
    # burn tracker, installed BEFORE serving starts so the first
    # decision can already be mirrored. Audit off = the doors' taps are
    # one None check (byte-identical hot path).
    auditor = None
    slo_tracker = None
    if args.audit:
        if args.backend not in ("sketch", "mesh"):
            raise SystemExit("--audit needs a sketch-family backend "
                             "(exact/dense decisions are already exact — "
                             "there is nothing to audit)")
        from ratelimiter_tpu.observability import audit as audit_mod
        from ratelimiter_tpu.observability.decorators import (
            undecorated as _undec,
        )
        from ratelimiter_tpu.observability.slo import SloBurnTracker

        n_sl = (len(slices) if slices is not None
                else len(_undec(limiter).sub_limiters()))
        auditor = audit_mod.enable(cfg, sample=args.audit_sample,
                                   n_slices=n_sl,
                                   include_twin=args.audit_twin,
                                   registry=obs_metrics.DEFAULT,
                                   # Follow runtime update_limit/window
                                   # (the decorator's config property
                                   # reflects the backend live).
                                   live_config=lambda: limiter.config)
        slo_tracker = SloBurnTracker(obs_metrics.DEFAULT)
        slo_tracker.attach()

    def make_audit_status(lims):
        """GET /debug/audit payload: rates + confidence + attribution,
        top-K consumers, SLO burn block — one JSON for the operator."""
        def _status() -> dict:
            out = auditor.status() if auditor is not None else {}
            out.update(_consumers_health(lims))
            out.update(_slo_health(slo_tracker))
            return out

        return _status

    dcn_secret = (args.dcn_secret
                  or os.environ.get("RATELIMITER_TPU_DCN_SECRET") or None)

    # Fleet tier (ADR-017): routing core + membership. Built before
    # either door so the doors' constructors take the core; the
    # membership announcer starts once serving does.
    fleet_core = None
    fleet_membership = None
    if args.fleet_config:
        if args.backend not in ("sketch", "mesh"):
            raise SystemExit("--fleet-config needs a sketch-family "
                             "backend (fleet routing hashes keys)")
        if not args.fleet_self:
            raise SystemExit("--fleet-config needs --fleet-self "
                             "(this server's host id in the map)")
        from ratelimiter_tpu.fleet import (
            FleetCore,
            FleetMap,
            FleetMembership,
        )

        fleet_map = FleetMap.load(args.fleet_config)
        fleet_core = FleetCore(
            fleet_map, args.fleet_self, prefix=cfg.prefix,
            forward=not args.fleet_no_forward,
            forward_deadline=args.fleet_forward_deadline,
            forward_queue=args.fleet_forward_queue,
            forward_inflight=args.fleet_forward_inflight,
            forward_conns=args.fleet_forward_conns,
            forward_coalesce=args.fleet_forward_coalesce,
            registry=obs_metrics.DEFAULT)
        # Placement load accounting (ADR-023): attached for EVERY fleet
        # member, not just --rebalance ones — any planning peer needs to
        # see this member's per-bucket load, and the /healthz placement
        # block + rate_limiter_placement_* families export either way.
        # Observation only: decisions and wire bytes are untouched.
        from ratelimiter_tpu.placement import LoadSlab

        fleet_core.load_slab = LoadSlab(fleet_map.buckets,
                                        registry=obs_metrics.DEFAULT)

        def _fleet_adopt(dead):
            """Failover standby unit: a fresh single-device sketch
            limiter restored from the dead host's newest snapshot + WAL
            suffix, PLUS any adopted-range aux units its manifest
            records — so a second failure after adoption keeps the
            adopted counters too (restore-before-rejoin, ADR-018).
            Restore failure (unreachable dir, a mesh peer's multi-file
            snapshot, drift) adopts FRESH state instead — under-counts
            only, the fail-toward-allowing direction; overrides are
            then absent until re-applied fleet-wide."""
            from ratelimiter_tpu.fleet.handoff import build_standby

            if dead.snapshot_dir:
                try:
                    unit = build_standby(cfg, dead.snapshot_dir)
                    logging.getLogger("ratelimiter_tpu.fleet").warning(
                        "fleet: adopted %s's ranges from %s",
                        dead.id, dead.snapshot_dir)
                    return unit
                except Exception:
                    logging.getLogger(
                        "ratelimiter_tpu.fleet").exception(
                        "fleet: restore of %s's snapshot dir %s failed; "
                        "adopting with fresh state", dead.id,
                        dead.snapshot_dir)
            return create_limiter(cfg, backend="sketch")

        def _handoff_restore(payload):
            """Incoming handoff (migration / departure / rejoin,
            ADR-018): restore the moved ranges' state from the sender's
            snapshot dir — its own unit (+ aux folds) for a migration
            or departure, or exactly OUR aux unit for a rejoin
            give-back. Reset replay applies only where the moved
            ranges own the key."""
            from ratelimiter_tpu.fleet.handoff import build_standby

            dir_ = payload.get("snapshot_dir")
            if not dir_:
                return None
            origin = payload.get("origin")
            owns = None
            if origin:
                ranges = [tuple(r) for r in payload.get("ranges", [])]
                buckets = fleet_core.map.buckets

                def owns(key: str) -> bool:
                    b = int(fleet_core.hash_keys([key])[0] % buckets)
                    return any(lo <= b < hi for lo, hi in ranges)

            return build_standby(cfg, dir_, origin=origin, owns=owns)

        def _absorb(unit):
            """Rejoin give-back: fold the returned ranges' state into
            the main serving limiter (conservative union) so they run
            the full pipelined path and ride the normal snapshot
            files. Only for the single-unit sketch backend — a sliced
            mesh or multi-shard door keeps the adopted-standby mount
            (folding one unit into every slice would inflate them
            all)."""
            if args.backend != "sketch" or (args.native
                                            and args.shards > 1):
                return False
            from ratelimiter_tpu.observability.decorators import (
                undecorated as _undec,
            )
            from ratelimiter_tpu.parallel import reshard

            _, arrays, extra = unit.capture_state()
            reshard.merge_into_limiter(_undec(limiter), arrays, extra)
            return True

        fleet_membership = FleetMembership(
            fleet_core, heartbeat=args.fleet_heartbeat,
            dead_after=args.fleet_dead_after,
            boot_grace=args.fleet_boot_grace, adopt_fn=_fleet_adopt,
            snapshot_fn=(persist.snapshot_now if persist is not None
                         else None),
            handoff_restore_fn=_handoff_restore,
            on_adopt=((lambda origin, unit, ranges:
                       persist.add_aux_unit(origin, unit, ranges))
                      if persist is not None else None),
            on_release=(persist.remove_aux_unit
                        if persist is not None else None),
            absorb_fn=_absorb,
            auto_rejoin=(args.fleet_rejoin == "auto"),
            secret=dcn_secret, registry=obs_metrics.DEFAULT)
        if not args.native and args.inflight < 2:
            # The fleet-merge side pool (the symmetric-forwarding
            # deadlock fix) only exists on the pipelined path; the
            # synchronous one-executor path can wedge two members on
            # each other under saturated mixed traffic until the
            # forward deadline degrades the rows.
            logging.getLogger("ratelimiter_tpu.fleet").warning(
                "fleet on the asyncio door with --inflight 1: forwarded "
                "frames block the single dispatch executor; use "
                "--inflight >= 2 for mixed/mis-routed traffic")

    def _fleet_health() -> dict:
        if fleet_core is None:
            return {}
        return {"fleet": {**fleet_core.status(),
                          **fleet_membership.status()}}

    # Placement (ADR-023): per-member load slab block (+ controller
    # status when the rebalancer runs here). Late-bound cell like the
    # tower's health: the controller is built with the door below.
    _rebalance_ctl = [None]

    def _placement_health() -> dict:
        if fleet_core is None or fleet_core.load_slab is None:
            return {}
        blk = fleet_core.load_slab.snapshot()
        if _rebalance_ctl[0] is not None:
            blk["rebalance"] = _rebalance_ctl[0].status()
        return {"placement": blk}

    def _make_rebalance(tower):
        """(controller, gateway hook) for the placement brain. The
        controller exists when this is a fleet member AND the operator
        asked for it (--rebalance background loop, or just
        --http-rebalance-token for a manual dry-run/apply surface)."""
        if fleet_core is None or fleet_core.load_slab is None:
            return None, None
        if not (args.rebalance or args.http_rebalance_token):
            return None, None
        from ratelimiter_tpu.placement import (
            PlannerKnobs,
            RebalanceController,
        )

        if tower is None and len(fleet_core.map.hosts) > 1:
            logging.getLogger("ratelimiter_tpu.placement").warning(
                "rebalance on a multi-member fleet without --http-port: "
                "peers' load blocks are unreachable, so every cycle "
                "skips on load-gap (wire an HTTP gateway and declare "
                "\"http\" ports in the fleet map)")
        ctl = RebalanceController(
            fleet_core, fleet_membership, fleet_core.load_slab,
            interval=args.rebalance_interval,
            knobs=PlannerKnobs(
                max_moves=args.rebalance_max_moves,
                trigger_ratio=args.rebalance_trigger,
                target_ratio=args.rebalance_target,
                min_residency_s=args.rebalance_min_residency),
            seed=args.rebalance_seed,
            fetch_peer_health=(
                (lambda: tower._fetch_all("/healthz", None))
                if tower is not None else None),
            slo_status=(slo_tracker.status if slo_tracker is not None
                        else None),
            audit_status=(auditor.status if auditor is not None
                          else None),
            registry=obs_metrics.DEFAULT)
        _rebalance_ctl[0] = ctl

        def hook(action: str) -> dict:
            if action == "status":
                return {"ok": True, "auto": bool(args.rebalance),
                        **ctl.status()}
            if action == "dry-run":
                return ctl.dry_run()
            if action == "apply":
                return ctl.apply()
            if action == "abort":
                return ctl.abort()
            return {"ok": False, "error": f"unknown action {action!r}"}

        return ctl, hook

    # Member identity (ADR-021): /healthz "member" block + the
    # rate_limiter_member_info identity gauge.
    member_info = _make_member_info(args, fleet_core)

    def _make_tower():
        """Fleet control tower (ADR-021): rollup/trace/event fan-out
        over the peers' declared HTTP gateways. None off-fleet or
        without a local gateway."""
        if fleet_core is None or args.http_port is None:
            return None
        from ratelimiter_tpu.fleet.tower import ControlTower

        me = fleet_core.map.host(args.fleet_self)
        if me.http != args.http_port:
            logging.getLogger("ratelimiter_tpu.fleet").warning(
                "fleet map entry %r declares http=%s but this server "
                "serves HTTP on %s — peers' fleet rollups/trace "
                "stitching will miss this member until the map's "
                "\"http\" field matches", args.fleet_self, me.http,
                args.http_port)
        return ControlTower(fleet_core, fleet_membership,
                            self_health=lambda: _tower_health[0]())

    # Late-bound: the health lambda is built with the door below; the
    # tower reads it through this cell so construction order stays
    # simple.
    _tower_health = [lambda: {}]

    http_reset = bool(args.http_reset or args.http_reset_token)
    http_policy = bool(args.http_policy or args.http_policy_token)
    dcn_peers = []
    if args.dcn_peer:
        from ratelimiter_tpu.serving.dcn_peer import parse_peer

        if args.backend not in ("sketch", "mesh"):
            # The mesh backend's slices are plain sketch limiters, each
            # exporting completed slabs / debt deltas (incl. promoted
            # heavy hitters via hh_owner2) — one pusher per slice below.
            raise SystemExit("--dcn-peer needs a sketch-family backend "
                             "(--backend sketch or --backend mesh)")
        dcn_peers = [parse_peer(s) for s in args.dcn_peer]
    pushers = []
    if args.native:
        from ratelimiter_tpu.serving.native_server import NativeRateLimitServer

        if fleet_core is not None:
            # ADR-019 columnar-forwarding contract: peers hash-forward
            # this member's STRING rows on the raw-id lane unless its
            # map entry declares shards > 1 (FNV string routing). An
            # undeclared multi-shard member would silently split a
            # key's quota across shards — refuse to start instead.
            actual = len(slices) if mesh_native else args.shards
            declared = fleet_core.map.host(args.fleet_self).shards
            if actual > 1 and declared != actual:
                raise SystemExit(
                    f"--fleet-config entry {args.fleet_self!r} declares "
                    f"shards={declared} but this native door runs "
                    f"{actual} shards; set \"shards\": {actual} on this "
                    f"host in the fleet map so peers forward its string "
                    f"rows as strings (ADR-019)")

        server = NativeRateLimitServer(
            limiter, args.listen or args.host, args.port,
            shm=args.shm, shm_dir=args.shm_dir,
            shm_ring_bytes=args.shm_ring_bytes,
            max_batch=args.max_batch, max_delay=args.max_delay_us * 1e-6,
            dispatch_timeout=(args.dispatch_timeout_ms * 1e-3
                              if args.dispatch_timeout_ms else None),
            inflight=args.inflight,
            shards=(len(slices) if mesh_native else args.shards),
            # Fleet membership gossips over the DCN channel, so a fleet
            # member always listens for pushes.
            net_engine=args.net_engine, io_rings=args.io_rings,
            dcn=bool(args.dcn_listen or args.dcn_peer or fleet_core),
            dcn_secret=dcn_secret,
            max_dcn_conns=args.dcn_max_transfers,
            fleet=fleet_core,
            fleet_announce=(fleet_membership.handle_announce
                            if fleet_membership is not None else None),
            # Mesh: the pre-built per-device slices ARE the shards, each
            # wearing the same decorator stack (+ persistence wrapper)
            # under its own shard label.
            shard_limiters=([limiter] + [decorate(s, shard=i)
                                         for i, s in enumerate(
                                             slices[1:], start=1)]
                            if mesh_native else None),
            # Clone shards get the same decorator stack as shard 0, so
            # /metrics and the breaker see all N shards' traffic (each
            # under its own shard label) — plus the persistence wrapper,
            # so a mutation on ANY shard reaches the WAL.
            shard_decorate=(lambda lim, i: decorate(lim, shard=i)))
        if persist is not None:
            # Recover BEFORE the listener opens: replayed mutations and
            # the restored snapshot must precede the first decision.
            persist.attach(server.shard_limiters, shard_of=server.shard_of)
            persist.recover()
            persist.start()
        server.start()
        if qmgr is not None:
            # Mirror quarantine transitions into the C++ door's stats
            # and wire restore-before-rejoin to the durability tier.
            qmgr.on_state_change = (
                lambda i, st: server.set_shard_health(i, st != "healthy"))
            if persist is not None:
                qmgr.restore_fn = persist.slice_restorer()
        if dcn_peers:
            # One pusher PER SHARD limiter: keys are hash-routed across
            # shards, so exporting shard 0 alone would hide (N-1)/N of
            # local traffic from every peer.
            from ratelimiter_tpu.observability.decorators import undecorated
            from ratelimiter_tpu.serving.dcn_peer import DcnPusher

            for shard_lim in server.shard_limiters:
                pushers.append(DcnPusher(
                    undecorated(shard_lim), dcn_peers,
                    interval=args.dcn_interval, secret=dcn_secret))
            for pu in pushers:
                pu.start()
        # Client-embedded quota leases (ADR-022): the C++ door has no
        # lease lane, so grants/renewals/returns serve from a sidecar
        # listener; revocation gossip and epoch checks still ride the
        # door's DCN receive path (server.leases). Debits route
        # through decide_one — the shard router — so a lease budget
        # lands on the key's owning shard.
        lease_mgr = _setup_leases(
            args, limiter=limiter, decide=server.decide_one,
            fleet_core=fleet_core, pushers=pushers, persist=persist)
        server.leases = lease_mgr
        lease_listener = None
        if lease_mgr is not None:
            from ratelimiter_tpu.leases.listener import LeaseListener

            lease_listener = LeaseListener(lease_mgr, host=args.host,
                                           port=args.lease_port or 0)
            lease_listener.start()
        # Hierarchical cascades (ADR-020): management surface over every
        # dispatch shard + the optional AIMD controller. After recovery
        # (hier_* checkpoint columns restore first), before the gateway
        # (whose /healthz and /v1/tenants mount it).
        hier, controller = _setup_hierarchy(
            args, cfg, server.shard_limiters, slo_tracker=slo_tracker,
            auditor=auditor, fleet_membership=fleet_membership)
        if controller is not None:
            controller.on_tighten = _lease_controller_hook(lease_mgr)
        # Policy/reset levers revoke the touched key's leases: HTTP and
        # gRPC get the wrapped callables here; a mutation arriving over
        # the C++ door's own binary lane is bounded by the lease TTL
        # instead (the asyncio door revokes inline).
        lease_set, lease_del = _lease_guarded_policy(
            lease_mgr, server.set_override_all,
            server.delete_override_all)
        lease_reset = _lease_guarded_reset(lease_mgr, server.reset_one)
        fleet_migrate = _make_fleet_migrate(args, fleet_core,
                                            fleet_membership)
        gateway = None
        if args.http_port is not None:
            from ratelimiter_tpu.serving.http_gateway import HttpGateway

            # decide/reset route through the server's shard router, so a
            # key's quota lives on ONE shard no matter which surface
            # (binary or HTTP) served it.
            def health_fn() -> dict:
                return {"serving": True,
                        **{k: v for k, v in server.stats().items()
                           if k == "decisions_total"},
                        "policy_overrides":
                            server.shard_limiters[0].override_count(),
                        "transport": server.transport_stats(),
                        "member": member_info(),
                        **_envelope_health(server.shard_limiters),
                        **_debt_slab_health(server.shard_limiters),
                        **_consumers_health(server.shard_limiters),
                        **_audit_health(),
                        **_slo_health(slo_tracker),
                        **_hierarchy_health(hier, controller),
                        **_lease_health(lease_mgr),
                        **_fleet_health(),
                        **_placement_health(),
                        **_events_health(),
                        **({"quarantine": qmgr.status()}
                           if qmgr is not None else {}),
                        **(persist.status() if persist else {})}

            _tower_health[0] = health_fn
            tower = _make_tower()
            rebal_ctl, fleet_rebalance = _make_rebalance(tower)
            gateway = HttpGateway(
                server.decide_one, lease_reset,
                host=args.host, port=args.http_port,
                metrics_render=obs_metrics.DEFAULT.render,
                health=health_fn,
                fleet_status=(tower.fleet_status if tower else None),
                fleet_trace=(tower.fleet_trace if tower else None),
                fleet_events=(tower.fleet_events if tower else None),
                enable_reset=http_reset,
                reset_token=args.http_reset_token,
                # Overrides apply on every shard (keys hash-route).
                policy_set=lease_set,
                policy_get=server.get_override_one,
                policy_delete=lease_del,
                enable_policy=http_policy,
                policy_token=args.http_policy_token,
                snapshot=(persist.snapshot_now if persist else None),
                snapshot_token=args.http_snapshot_token,
                enable_debug=http_debug,
                debug_token=args.debug_token,
                audit_status=(make_audit_status(server.shard_limiters)
                              if args.audit else None),
                audit_token=args.audit_token,
                tenants=hier,
                enable_tenants=bool(args.http_tenants
                                    or args.http_tenants_token),
                tenants_token=args.http_tenants_token,
                fleet_migrate=fleet_migrate,
                migrate_token=args.http_migrate_token,
                fleet_rebalance=fleet_rebalance,
                rebalance_token=args.http_rebalance_token)
            gateway.start()
        else:
            rebal_ctl = None
        grpc_srv = None
        if args.grpc_port is not None:
            from ratelimiter_tpu.serving.grpc_server import GrpcRateLimitServer

            grpc_srv = GrpcRateLimitServer(
                server.decide_one, lease_reset,
                host=args.host, port=args.grpc_port,
                decisions_total=lambda: server.stats().get(
                    "decisions_total", 0),
                decide_many=server.decide_many,
                policy=(lease_set, server.get_override_one, lease_del),
                default_limit=lambda: limiter.config.limit,
                tenants=hier)
            grpc_srv.start()
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            loop.add_signal_handler(sig, stop.set)
        net_info = (server.transport_stats() or {}).get("net", {})
        print(f"serving(native) {args.algorithm}/{args.backend} "
              f"limit={args.limit}/{args.window:g}s on "
              + (args.listen if args.listen
                 else f"{args.host}:{server.port}")
              + (f" net={net_info.get('engine', '?')}"
                 f"x{net_info.get('rings', '?')}"
                 f"(probe={net_info.get('uring_probe', '?')})")
              + f" {device_report}"
              + (" shm" if args.shm else "")
              + (f" http:{gateway.port}" if gateway else "")
              + (f" grpc:{grpc_srv.port}" if grpc_srv else "")
              + (f" lease:{lease_listener.port}" if lease_listener
                 else ""), flush=True)
        if fleet_membership is not None:
            fleet_membership.start()
        if controller is not None:
            controller.start()
        if rebal_ctl is not None and args.rebalance:
            rebal_ctl.start()
        if start_chaos is not None:
            start_chaos()
        await stop.wait()
        if rebal_ctl is not None:
            # Before departure: a mid-shutdown plan must not race the
            # departure handoff for the same ranges.
            rebal_ctl.stop()
        if controller is not None:
            # Before the doors drain: a controller tick against a
            # closing limiter would race teardown.
            controller.stop()
        if fleet_membership is not None:
            # Departure announce BEFORE the doors close (ADR-018): hand
            # our ranges to the successor (final-ish snapshot + restore
            # on its side + epoch bump), so a rolling restart never
            # leaves an ownership hole — in-flight rows ride the
            # forward/redirect window while we drain below. Runs in a
            # thread so the event loop keeps receiving the flip
            # announce the wait depends on.
            await asyncio.get_running_loop().run_in_executor(
                None, lambda: fleet_membership.depart(
                    wait=max(2.0, 4 * args.fleet_heartbeat)))
            fleet_membership.stop()
        for pu in pushers:
            pu.stop()
        if gateway is not None:
            gateway.shutdown()
        if grpc_srv is not None:
            grpc_srv.shutdown()
        if lease_mgr is not None:
            # Revoke-all BEFORE the listener closes: holders get the
            # shutdown push and stop answering locally right away
            # instead of riding out their TTL.
            lease_mgr.close()
        if lease_listener is not None:
            lease_listener.close()
        if persist is not None:
            # Stop the C++ door FIRST (answers in-flight work), then the
            # final snapshot: every acknowledged decision is captured —
            # a graceful shutdown loses nothing. Shard clones close
            # after the capture.
            server.shutdown(close_limiters=False)
            persist.stop()
            server.close_shards()
        else:
            server.shutdown()
        if fleet_core is not None:
            # After the door drains: in-flight frames may still hold
            # forward futures.
            fleet_core.close()
        if auditor is not None:
            from ratelimiter_tpu.observability import audit as audit_mod

            auditor.flush(timeout=2.0)
            audit_mod.disable()
        if slo_tracker is not None:
            slo_tracker.detach()
        limiter.close()
        return
    if args.shards > 1:
        raise SystemExit("--shards needs --native (the asyncio front door "
                         "has one dispatcher)")
    if dcn_peers:
        from ratelimiter_tpu.observability.decorators import undecorated
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher

        # Mesh composite: one pusher PER SLICE (keys hash-route across
        # devices, so exporting one slice would hide (N-1)/N of local
        # traffic from every peer — same rule as the native door's
        # per-shard pushers).
        for push_lim in undecorated(limiter).sub_limiters():
            pushers.append(DcnPusher(push_lim, dcn_peers,
                                     interval=args.dcn_interval,
                                     secret=dcn_secret))
        for pu in pushers:
            pu.start()
    if persist is not None:
        persist.attach([limiter])
        persist.recover()
        persist.start()
        if qmgr is not None:
            # Restore-before-rejoin (ADR-015): a recovering slice
            # replays the newest snapshot + WAL suffix before routing.
            qmgr.restore_fn = persist.slice_restorer()
    if fleet_core is not None:
        # Wrap AFTER recovery: WAL replay must apply locally, never
        # forward (a replayed reset for a now-foreign key belongs to
        # history, not to a peer). Outermost of the whole stack — the
        # batcher's frames partition by owner before anything local
        # runs.
        from ratelimiter_tpu.fleet import FleetForwarder

        limiter = FleetForwarder(limiter, fleet_core)
    server = RateLimitServer(
        limiter, args.listen or args.host, args.port,
        shm=args.shm, shm_dir=args.shm_dir,
        shm_ring_bytes=args.shm_ring_bytes,
        max_batch=wait_rows,
        max_delay=args.max_delay_us * 1e-6,
        dispatch_timeout=(args.dispatch_timeout_ms * 1e-3
                          if args.dispatch_timeout_ms else None),
        inflight=args.inflight,
        dcn=bool(args.dcn_listen or args.dcn_peer or fleet_core),
        dcn_secret=dcn_secret,
        snapshot=(persist.snapshot_now if persist else None),
        fleet=fleet_core,
        fleet_announce=(fleet_membership.handle_announce
                        if fleet_membership is not None else None))
    loop = asyncio.get_running_loop()

    # Gateway/gRPC worker threads funnel into the SAME micro-batcher as
    # the binary protocol: all surfaces share device dispatches.
    threadsafe_decide = make_threadsafe_decide(server.batcher, loop)

    # Client-embedded quota leases (ADR-022): the asyncio door serves
    # lease frames on its main port (no sidecar). Debits ride the
    # shared micro-batcher — the lease handler runs on an executor
    # thread, so the threadsafe bridge is the right decide path.
    lease_mgr = _setup_leases(
        args, limiter=limiter, decide=threadsafe_decide,
        fleet_core=fleet_core, pushers=pushers, persist=persist)
    server.leases = lease_mgr
    await server.start()

    gateway = None
    grpc_srv = None

    # Hierarchical cascades (ADR-020) on the asyncio door: ONE dispatch
    # unit (a SlicedMeshLimiter already spans its slices write-all, and
    # the FleetForwarder decorator delegates inward). After recovery, so
    # boot flags win over a snapshot's registry for the names they touch.
    hier, controller = _setup_hierarchy(
        args, cfg, [limiter], slo_tracker=slo_tracker, auditor=auditor,
        fleet_membership=fleet_membership)
    if controller is not None:
        controller.on_tighten = _lease_controller_hook(lease_mgr)
    # HTTP/gRPC policy + reset levers revoke the touched key's leases
    # (the binary door's T_POLICY/T_RESET handlers revoke inline).
    lease_set, lease_del = _lease_guarded_policy(
        lease_mgr, limiter.set_override, limiter.delete_override)
    lease_reset = _lease_guarded_reset(lease_mgr, limiter.reset)
    fleet_migrate = _make_fleet_migrate(args, fleet_core, fleet_membership)

    if args.http_port is not None:
        from ratelimiter_tpu.serving.http_gateway import HttpGateway

        def health_fn() -> dict:
            return {"serving": True,
                    "decisions_total": server.batcher.decisions_total,
                    "policy_overrides": limiter.override_count(),
                    "transport": server.transport_stats(),
                    "member": member_info(),
                    **_envelope_health([limiter]),
                    **_debt_slab_health([limiter]),
                    **_consumers_health([limiter]),
                    **_audit_health(),
                    **_slo_health(slo_tracker),
                    **_hierarchy_health(hier, controller),
                    **_lease_health(lease_mgr),
                    **_fleet_health(),
                    **_placement_health(),
                    **_events_health(),
                    **({"quarantine": qmgr.status()}
                       if qmgr is not None else {}),
                    **(persist.status() if persist else {})}

        _tower_health[0] = health_fn
        tower = _make_tower()
        rebal_ctl, fleet_rebalance = _make_rebalance(tower)
        gateway = HttpGateway(
            threadsafe_decide, lease_reset,
            host=args.host, port=args.http_port,
            metrics_render=obs_metrics.DEFAULT.render,
            health=health_fn,
            fleet_status=(tower.fleet_status if tower else None),
            fleet_trace=(tower.fleet_trace if tower else None),
            fleet_events=(tower.fleet_events if tower else None),
            enable_reset=http_reset,
            reset_token=args.http_reset_token,
            policy_set=lease_set,
            policy_get=limiter.get_override,
            policy_delete=lease_del,
            enable_policy=http_policy,
            policy_token=args.http_policy_token,
            snapshot=(persist.snapshot_now if persist else None),
            snapshot_token=args.http_snapshot_token,
            enable_debug=http_debug,
            debug_token=args.debug_token,
            audit_status=(make_audit_status([limiter])
                          if args.audit else None),
            audit_token=args.audit_token,
            tenants=hier,
            enable_tenants=bool(args.http_tenants
                                or args.http_tenants_token),
            tenants_token=args.http_tenants_token,
            fleet_migrate=fleet_migrate,
            migrate_token=args.http_migrate_token,
            fleet_rebalance=fleet_rebalance,
            rebalance_token=args.http_rebalance_token)
        gateway.start()
    else:
        rebal_ctl = None
    if args.grpc_port is not None:
        from ratelimiter_tpu.serving.grpc_server import GrpcRateLimitServer

        grpc_srv = GrpcRateLimitServer(
            threadsafe_decide, lease_reset,
            host=args.host, port=args.grpc_port,
            decisions_total=lambda: server.batcher.decisions_total,
            decide_many=make_threadsafe_decide_many(server.batcher, loop),
            policy=(lease_set, limiter.get_override, lease_del),
            default_limit=lambda: limiter.config.limit,
            tenants=hier)
        grpc_srv.start()

    stop = asyncio.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig, stop.set)
    print(f"serving {args.algorithm}/{args.backend} "
          f"limit={args.limit}/{args.window:g}s on "
          + (args.listen if args.listen
             else f"{args.host}:{server.port}")
          + f" {device_report}"
          + (" shm" if args.shm else "")
          + (f" http:{gateway.port}" if gateway else "")
          + (f" grpc:{grpc_srv.port}" if grpc_srv else ""), flush=True)
    if fleet_membership is not None:
        fleet_membership.start()
    if controller is not None:
        controller.start()
    if rebal_ctl is not None and args.rebalance:
        rebal_ctl.start()
    if start_chaos is not None:
        start_chaos()
    await stop.wait()
    if rebal_ctl is not None:
        # Before departure: a mid-shutdown plan must not race the
        # departure handoff for the same ranges.
        rebal_ctl.stop()
    if controller is not None:
        # Before the door drains: a controller tick against a closing
        # limiter would race teardown.
        controller.stop()
    if fleet_membership is not None:
        # Departure announce BEFORE the door drains (ADR-018) — see the
        # native path above; off-loop so the server keeps receiving the
        # flip announce.
        await asyncio.get_running_loop().run_in_executor(
            None, lambda: fleet_membership.depart(
                wait=max(2.0, 4 * args.fleet_heartbeat)))
        fleet_membership.stop()
    for pu in pushers:
        pu.stop()
    if gateway is not None:
        gateway.shutdown()
    if grpc_srv is not None:
        grpc_srv.shutdown()
    await server.shutdown()
    if persist is not None:
        # After drain, before close: the final snapshot captures every
        # answered decision — a graceful shutdown loses nothing.
        persist.stop()
    if auditor is not None:
        from ratelimiter_tpu.observability import audit as audit_mod

        auditor.flush(timeout=2.0)
        audit_mod.disable()
    if slo_tracker is not None:
        slo_tracker.detach()
    limiter.close()


def main() -> None:
    asyncio.run(amain(build_parser().parse_args()))


if __name__ == "__main__":
    main()
