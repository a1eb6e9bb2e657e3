"""Python bridge for the native (C++) front door.

The C++ extension (native/server.cpp) owns sockets, frame parsing,
micro-batch coalescing, and response encoding in GIL-free threads;
Python is entered once per batched dispatch through the callbacks this
module builds. Same protocol, same semantics, same test suite as the
asyncio server (serving/server.py) — the asyncio server remains the
reference implementation; this one is the throughput path
(ROADMAP "server hot-path in C++").

Hot path: the decide/launch callbacks receive the batch as four flat
buffers (key blob + offsets + lengths + ns). For sketch-family limiters
the keys never become Python strings: the blob is prefix-packed with
NumPy and bulk-hashed (native hasher) straight into ``allow_hashed`` /
``launch_hashed``. Other backends decode to strings and use
``allow_batch``.

Pipelined mode (default for sketch backends without an SLO, ADR-010):
the C++ dispatcher calls ``launch`` (non-blocking — stage + enqueue the
jitted step) and a C++ completer thread calls ``resolve`` on the oldest
in-flight ticket, so up to ``inflight`` device dispatches overlap with
host encode/decode instead of the old launch→block→serialize lockstep.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Optional

import numpy as np

from ratelimiter_tpu.algorithms.base import RateLimiter
from ratelimiter_tpu.core.errors import (
    InvalidKeyError,
    InvalidNError,
)
from ratelimiter_tpu.observability import audit, tracing
from ratelimiter_tpu.observability import metrics as m
from ratelimiter_tpu.serving import protocol as p


_ABI = 14

#: The coalescer's rule when ``--max-batch`` is not given: a queue that
#: holds ``AUTO_WAIT_ROWS`` keys dispatches at once (a thinner one waits
#: up to ``--max-delay-us``), and a drain takes every whole item that is
#: already queued, up to ``AUTO_DRAIN_ROWS`` rows. Nothing waits for the
#: larger run: it is what piled up while the dispatcher was in a launch.
AUTO_WAIT_ROWS = 4096
AUTO_DRAIN_ROWS = 16384


def batch_rule(max_batch: Optional[int], *, native: bool = True,
               slo: bool = False) -> tuple:
    """``(wait threshold, drain cap)`` of a door's coalescer.

    An explicit ``max_batch`` is the operator's cap and the wait
    threshold both. ``None`` is the default rule above on the native
    door; it stays one frame's worth a dispatch, (4,096, 4,096), under a
    dispatch SLO — a deadline per dispatch, set against runs of at most
    the wait threshold — and on the asyncio door, whose batcher has one
    number. What the door observes (a flag, an SLO) decides, never the
    limiter behind it. Prewarm covers every pad shape up to twice the
    drain cap (serving/__main__.py:_prewarm)."""
    if max_batch is not None:
        return max_batch, max_batch
    return AUTO_WAIT_ROWS, (AUTO_DRAIN_ROWS if native and not slo
                            else AUTO_WAIT_ROWS)


@functools.lru_cache(maxsize=None)
def _load_extension():
    """The ``native/_server.so`` extension module, built from this
    checkout's server.cpp + shm_ring.h (native/build.py decides
    staleness by source hash), or None on a host that cannot build it.
    Compiler and loader errors propagate."""
    import os

    from ratelimiter_tpu.native.build import load_extension

    d = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
    loaded = load_extension(
        os.path.join(d, "_server.so"),
        [os.path.join(d, "server.cpp"), os.path.join(d, "shm_ring.h")],
        module="ratelimiter_tpu.native._server",
        abi_symbol="rl_server_abi_version", abi=_ABI)
    return loaded[1] if loaded is not None else None


def native_server_available() -> bool:
    return _load_extension() is not None


class _BridgeError(Exception):
    """Carries a protocol error code for the C++ layer (read via
    ``rl_code``)."""

    def __init__(self, code: int, msg: str):
        super().__init__(msg)
        self.rl_code = code


class NativeRateLimitServer:
    """Drop-in sibling of RateLimitServer backed by the C++ front door.

    Args mirror RateLimitServer, including ``dispatch_timeout``: a C++
    watcher thread answers waiters per the limiter's fail-open/closed
    policy when one batched dispatch exceeds the SLO, while the Python
    decide completes in the background (state still converges). The
    ``limit``/``window`` stamped into fail-open responses are LIVE when
    updated through THIS server's ``update_limit``/``update_window``
    (eager push to the C++ atomics). A direct ``limiter.update_limit``
    also converges after the next completed dispatch (results carry the
    limit); a direct ``limiter.update_window`` does NOT — the result
    tuple carries no window, so use the server wrapper for window
    changes. Per-key policy-override limits are never reflected in
    fail-open stamps (the dispatch that would resolve them never
    completed; the decision fields are policy-driven either way).

    ``max_batch`` given is the coalescer's one number, as in the asyncio
    door; left ``None`` the door sizes a run from its own queue
    (``batch_rule``; ``self.max_batch``, ``self.drain_cap``).

    ``inflight`` (default 8; >1 requires a sketch-family limiter and no
    dispatch_timeout) enables the pipelined launch/resolve hot path:
    that many device dispatches stay in flight per shard, with
    backpressure upstream of the sockets when the window fills.

    ``shard_limiters`` mounts PRE-BUILT per-shard limiters instead of
    cloning from ``limiter`` — the slice-parallel mesh backend passes
    its device-pinned slices here, making one dispatch shard == one
    device (ADR-012); ``limiter`` must then be element 0 of the list.
    """

    def __init__(self, limiter: RateLimiter, host: str = "127.0.0.1",
                 port: int = 0, *, max_batch: Optional[int] = None,
                 max_delay: float = 200e-6,
                 dispatch_timeout: Optional[float] = None,
                 inflight: int = 8,
                 registry: Optional[m.Registry] = None,
                 shards: int = 1, dcn: bool = False,
                 dcn_secret: Optional[str] = None,
                 max_dcn_conns: int = 4,
                 shard_decorate=None,
                 shard_limiters: Optional[list] = None,
                 fleet=None, fleet_announce=None, leases=None,
                 shm: bool = False, shm_dir: str = "/dev/shm",
                 shm_ring_bytes: int = 0,
                 net_engine: str = "auto", io_rings: int = 0):
        ext = _load_extension()
        if ext is None:
            raise RuntimeError(
                "native server extension unavailable: no g++ here (or "
                "RATELIMITER_TPU_NO_BUILD=1) and no _server.so built "
                "from these sources; use the asyncio RateLimitServer")
        if inflight < 1:
            raise ValueError(f"inflight must be >= 1, got {inflight}")
        self.limiter = limiter
        self.host = host
        self.port = port
        self.registry = registry if registry is not None else m.DEFAULT
        self._batch_hist = self.registry.histogram(
            "rate_limiter_server_batch_size",
            "Decisions per batched dispatch", m.BATCH_BUCKETS)
        self._inflight_gauge = self.registry.gauge(
            "rate_limiter_pipeline_inflight",
            "Launched device dispatches not yet resolved (pipelined "
            "serving hot path, ADR-010)")
        self._launch_hist = self.registry.histogram(
            "rate_limiter_pipeline_launch_seconds",
            "Launch phase wall time (stage + enqueue, non-blocking)",
            m.LATENCY_BUCKETS)
        self._resolve_hist = self.registry.histogram(
            "rate_limiter_pipeline_resolve_seconds",
            "Resolve phase wall time (block on the oldest in-flight "
            "result + host conversion)", m.LATENCY_BUCKETS)
        self._depth = 0
        self._depth_lock = threading.Lock()

        # The device backends (the sketch family, the dense backend)
        # expose the hashed fast path; detect once on the UNDECORATED
        # backend (decorators delegate the whole hashed surface, so
        # hasattr on the stack is always true).
        from ratelimiter_tpu.observability.decorators import undecorated as _u

        self._fast = hasattr(_u(limiter), "allow_hashed")
        prefix = limiter.config.prefix
        self._prefix_bytes = (f"{prefix}:".encode() if prefix else b"")

        # Dispatch shards: keys are hash-routed in C++, each shard has
        # its own limiter instance and dispatcher thread, so shards
        # decide CONCURRENTLY (per-key semantics stay exact — a key
        # always lands on the same shard). The in-process analog of the
        # reference's Redis-Cluster keyspace sharding; on a multi-chip
        # box each shard maps naturally onto its own device. Extra shard
        # limiters are owned (and closed) by this server.
        #
        # ``shard_limiters`` supplies the per-shard limiters PRE-BUILT
        # instead of cloning — the slice-parallel mesh backend mounts
        # its device-pinned slices here (one shard == one device,
        # ADR-012), so the C++ shard router IS the shard→device router
        # and every dispatch runs collective-free on its owning chip.
        if shard_limiters is not None:
            if shards not in (1, len(shard_limiters)):
                raise ValueError(
                    f"shards={shards} disagrees with "
                    f"{len(shard_limiters)} supplied shard limiters")
            shards = len(shard_limiters)
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if shards > 1 and dispatch_timeout is not None:
            raise ValueError("dispatch_timeout requires shards == 1")
        from ratelimiter_tpu.observability.decorators import undecorated

        base = undecorated(limiter)
        if shard_limiters is not None:
            self._shard_limiters = list(shard_limiters)
        else:
            if shards > 1 and not getattr(base, "state_from_config", False):
                # Clones are rebuilt from (config, clock) alone; backends
                # with extra constructor state (e.g. the dense backend's
                # capacity override) would silently diverge between
                # shards. Pre-built shard limiters are nobody's clones.
                raise ValueError(
                    "shards > 1 requires a sketch-family limiter (its "
                    "state is fully determined by the config)")
            self._shard_limiters = [limiter]
            for i in range(1, shards):
                # Clones rebuilt from (config, clock); ``shard_decorate(
                # lim, shard_index)`` (e.g. the server binary's decorator
                # stack) wraps each one so observability sees EVERY
                # shard's traffic — per-shard labeled, not just the 1/N
                # of keys that land on the caller's limiter. Without it
                # the clones are raw state shards (the pre-r5 behavior).
                kw = {}
                if getattr(base, "_hier_table", None) is not None:
                    # Cascade scopes on a multi-shard door (ADR-020):
                    # every clone enforces the same per-shard share of
                    # the tenant/global limits as the base (keys hash-
                    # route, shards share no counters — the sliced-mesh
                    # static-split rule).
                    kw["hier_divisor"] = base._hier_table.divisor
                clone = type(base)(base.config, clock=base.clock, **kw)
                self._shard_limiters.append(
                    shard_decorate(clone, i) if shard_decorate else clone)
        self._locks = [threading.Lock() for _ in range(shards)]
        #: Per shard: the launch callback's stamps and the lane's (t_door,
        #: t_lane) of the ticket its completer resolved last (_resolve ->
        #: _spans, same thread, back to back).
        self._door_ns = [(None, None)] * shards

        # Fleet tier (ADR-017): the bridge partitions every decision
        # frame by keyspace owner BEFORE the shard limiter sees it —
        # the blob is still in hand here, so foreign STRING rows
        # forward as strings (a multi-shard receiver's FNV router then
        # lands them on the same shard as that key's direct traffic;
        # h64-routed forwarding would split a key's quota across two
        # shards). None = byte-identical hot path.
        self._fleet = fleet
        self._fleet_announce = fleet_announce
        #: LeaseManager (ADR-022). The compiled fast path knows nothing
        #: of lease frames — lease TRAFFIC enters via the LeaseListener
        #: sidecar port — but the DCN receive path here still applies
        #: revocation gossip and epoch checks against it.
        self.leases = leases

        # Fast path: C++ prepends the prefix while building the blob, so
        # the decide callback hashes ready-made bytes (the numpy re-pack
        # this replaces measured 7 ms per 4096 keys — the single largest
        # serving cost). Slow path: keys are decoded to strings and
        # allow_batch applies the prefix itself, so C++ must not.
        self.dcn = bool(dcn)
        self.dcn_secret = dcn_secret
        #: Replay guard for sequenced (RLA2) DCN pushes — per-sender
        #: monotonic watermarks, shared by every shard (ADR-007).
        self._dcn_guard = p.DcnReplayGuard() if dcn else None
        # Pipelined launch/resolve needs the hashed fast path (the launch
        # must be non-blocking, which the string slow path's allow_batch
        # is not) and no SLO (the C++ watcher assumes one dispatch in
        # flight); otherwise the legacy blocking decide runs.
        self.inflight = inflight
        self._pipelined = bool(self._fast and dispatch_timeout is None
                               and inflight > 1)
        self.max_batch, self.drain_cap = batch_rule(
            max_batch, slo=bool(dispatch_timeout))
        self._server = ext.create_server(
            decide=self._decide, reset=self._reset, metrics=self._metrics,
            max_batch=self.max_batch, drain_cap=self.drain_cap,
            max_delay_us=int(max_delay * 1e6),
            slo_us=int(dispatch_timeout * 1e6) if dispatch_timeout else 0,
            fail_open=bool(limiter.config.fail_open),
            limit=int(limiter.config.limit),
            window_s=float(limiter.config.window),
            key_prefix=self._prefix_bytes if self._fast else b"",
            num_shards=shards,
            dcn=self._dcn if dcn else None,
            launch=self._launch if self._pipelined else None,
            resolve=self._resolve if self._pipelined else None,
            # Hashed bulk lane (T_ALLOW_HASHED, ADR-011): the C++ door
            # finalizes raw ids with splitmix64 on its io threads and
            # hands COLUMNAR id/ns buffers straight to these callbacks —
            # no blob, no offsets, no host hashing.
            decide_hashed=self._decide_hashed if self._fast else None,
            launch_hashed=(self._launch_hashed_cb
                           if self._pipelined else None),
            # Per-ticket stage timestamps (ABI 9, ADR-014): the completer
            # reports io/dispatch/device/complete stamps per resolved
            # dispatch; _spans records them into the flight recorder
            # (no-op when tracing is off — one None check per dispatch).
            spans=self._spans if self._pipelined else None,
            inflight=inflight,
            dcn_auth_required=bool(dcn and dcn_secret),
            # Size to the DCN peer set: each peer holding a slab-sized
            # in-flight push needs a grant; the default covers small
            # meshes, a refused peer gets a typed error and retries next
            # cycle (watermarks re-send slabs; dcn_peer.py).
            max_dcn_conns=max(1, int(max_dcn_conns)),
            # Zero-syscall shared-memory lane (ADR-025): off by default;
            # when on, T_SHM_HELLO upgrades a connection to SPSC ring
            # pairs in /dev/shm carrying the SAME wire frames.
            shm=bool(shm), shm_dir=str(shm_dir),
            shm_ring_bytes=int(shm_ring_bytes),
            # Multi-ring network engine (ISSUE-20, ADR-026): backend
            # request ("auto" probes io_uring at start and falls back to
            # epoll with the reason recorded) + sharded io ring count
            # (0 = auto: min(4, hardware threads); 1 + epoll reproduces
            # the pre-ISSUE-20 single-loop behavior).
            net_engine=str(net_engine), io_rings=int(io_rings))
        self.net_engine = str(net_engine)
        self.io_rings = int(io_rings)
        self.shm = bool(shm)
        self.shm_dir = str(shm_dir)
        self.shm_ring_bytes = int(shm_ring_bytes)
        self.registry.add_collect_hook(self._collect_transport_metrics)

    # ------------------------------------------------------------ callbacks

    def _hash_buffers(self, blob: bytes, offsets_b: bytes,
                      lengths_b: bytes, ns_b: bytes):
        """C++ buffers -> (h64, ns): the no-string bulk-hash fast path
        (prefix already prepended by the C++ blob builder)."""
        from ratelimiter_tpu.native import hash_packed

        offsets = np.frombuffer(offsets_b, dtype=np.int64)
        lengths = np.frombuffer(lengths_b, dtype=np.int64)
        ns = np.frombuffer(ns_b, dtype=np.int64)
        buf = np.frombuffer(blob, dtype=np.uint8)
        return hash_packed(buf, offsets, lengths), ns

    def _pack_result(self, out):
        flags = out.allowed.astype(np.uint8)
        if out.fail_open:
            flags |= 2
        return (flags.tobytes(),
                np.ascontiguousarray(out.remaining, dtype=np.int64).tobytes(),
                np.ascontiguousarray(out.retry_after, dtype=np.float64).tobytes(),
                np.ascontiguousarray(out.reset_at, dtype=np.float64).tobytes(),
                int(out.limit))

    def _spans(self, shard: int, count: int, trace_id: int, t_io: int,
               t_d0: int, t_d1: int, t_v0: int, t_v1: int):
        """ABI 9 spans callback (ADR-014): per-ticket CLOCK_MONOTONIC
        stage stamps from the C++ completer — io (enqueue→drain),
        dispatch (drain→ticket pushed), enter and leave (the stage's two
        ends outside the launch callback), device (resolve blocking) and
        complete (resolve→now) — recorded into the flight recorder on
        the completer thread. Same clock domain as tracing.now()."""
        rec = tracing.RECORDER
        if rec is None:
            return
        if t_io and t_d0 >= t_io:
            rec.record("io", t_io, t_d0, trace_id=trace_id, shard=shard,
                       batch=count)
        rec.record("dispatch", t_d0, t_d1, trace_id=trace_id, shard=shard,
                   batch=count)
        door, lane = self._door_ns[shard]
        if door is not None and t_d0 <= door[0] <= door[2] <= t_d1:
            # What C++ holds of the stage, on either side of the launch
            # callback (_resolve left the ticket's stamps here just
            # before this call): the gather of the group's columns + the
            # wait for the GIL, and the GIL's release + the wait for a
            # slot of the in-flight window + the push.
            rec.record("enter", t_d0, door[0], trace_id=trace_id,
                       shard=shard, batch=count)
            if lane is not None and door[1] <= lane[0] <= lane[1] <= door[2]:
                # What the callback holds on either side of the lane's
                # launch (prep ... finish, the lane's own rows):
                # frombuffer, the shard lock and the decorators on the
                # way down; the decorators on the way up, the depth
                # lock, gauge and histogram.
                rec.record("descend", door[1], lane[0], trace_id=trace_id,
                           shard=shard, batch=count)
                rec.record("ascend", lane[1], door[2], trace_id=trace_id,
                           shard=shard, batch=count)
            rec.record("leave", door[2], t_d1, trace_id=trace_id,
                       shard=shard, batch=count)
        rec.record("device", t_v0, t_v1, trace_id=trace_id, shard=shard,
                   batch=count)
        rec.record("complete", t_v1, tracing.now(), trace_id=trace_id,
                   shard=shard, batch=count)

    # ------------------------------------------------- fleet split (ADR-017)

    def _keys_from_blob(self, blob: bytes, offsets: np.ndarray,
                        lengths: np.ndarray, pos: np.ndarray):
        """Recover the RAW key strings for the given rows (prefix
        stripped — the receiving server re-applies its identical
        prefix, so the forwarded key hashes bit-identically)."""
        pl = len(self._prefix_bytes)
        return [blob[int(offsets[i]) + pl:
                     int(offsets[i]) + int(lengths[i])].decode("utf-8")
                for i in pos.tolist()]

    def _fleet_split(self, h64: np.ndarray, ns: np.ndarray, *,
                     blob=None, offsets=None, lengths=None):
        """Partition one frame by fleet owner and fire the forwards.
        Returns ``(local_pos, jobs)``; ``(None, ())`` = whole frame
        local (caller keeps the untouched fast path). Raises the typed
        redirect in redirect-only mode."""
        import concurrent.futures as cf

        from ratelimiter_tpu.core.errors import StorageUnavailableError

        core = self._fleet
        owners = core.owners_of_hash(h64)
        if core.all_local(owners):
            return None, ()
        if not core.forward_enabled:
            # Typed redirect — but only for frames that actually carry
            # FOREIGN rows: with adopted ranges mounted, all_local() is
            # False for every frame (the adopted mask must be checked
            # row-wise), and a wholly-self-owned frame must fall
            # through to the split below, not bounce off itself. Raised
            # as the library error; every bridge caller wraps it into a
            # _BridgeError with the right wire code (code_for knows
            # E_NOT_OWNER).
            foreign = owners != core.self_ordinal
            if foreign.any():
                i = int(np.argmax(foreign))
                raise core.redirect_error(int(h64[i]), int(owners[i]))
        local_pos, adopted_pos, foreign = core.split(h64, owners)
        jobs = []
        if adopted_pos.shape[0]:
            jobs.append((adopted_pos,
                         core.decide_adopted_hashed(h64[adopted_pos],
                                                    ns[adopted_pos]),
                         None))
        # String rows carry a LAZY key extractor: the coalesced lane
        # (ADR-019) hash-forwards them columnar to single-shard peers
        # without ever decoding the key blob — keys materialize only
        # for a peer that declared shards > 1 (FNV-routed strings).
        keys_fn = (None if blob is None else
                   (lambda pos_: self._keys_from_blob(blob, offsets,
                                                      lengths, pos_)))
        for o, pos in foreign.items():
            if o in core._dead_ordinals:
                fut = cf.Future()
                fut.set_exception(StorageUnavailableError(
                    f"fleet owner {core.map.hosts[o].id} is down "
                    f"(failover pending)"))
                jobs.append((pos, fut, o))
                continue
            for sub_pos, fut in core.forward_jobs(o, pos, h64, ns,
                                                  keys_fn=keys_fn):
                jobs.append((sub_pos, fut, o))
        return local_pos, jobs

    def _fleet_decide(self, shard: int, h64: np.ndarray, ns: np.ndarray,
                      local_pos: np.ndarray, jobs):
        """Blocking fleet decide: local rows dispatch on the shard
        limiter WHILE the forwards (already in flight) overlap their
        network RTT with the device step; merge in frame order."""
        from ratelimiter_tpu.fleet.forwarder import (
            collect_jobs,
            scatter_merge,
        )

        lim = self._shard_limiters[shard]
        now = lim.clock.now()
        parts = []
        err = None
        if local_pos.shape[0]:
            try:
                with self._locks[shard]:
                    parts.append((local_pos,
                                  lim.allow_hashed(h64[local_pos],
                                                   ns[local_pos])))
            except Exception as exc:  # noqa: BLE001 — drain forwards first
                err = exc
        fparts, ferr = collect_jobs(self._fleet, jobs, lim.config, now)
        parts.extend(fparts)
        err = err if err is not None else ferr
        if err is not None:
            raise err
        return scatter_merge(int(h64.shape[0]), lim.config.limit, parts)

    def _fleet_launch(self, shard: int, h64: np.ndarray, ns: np.ndarray,
                      *, blob=None, offsets=None, lengths=None):
        """Pipelined fleet launch: local rows launch on the shard
        limiter (non-blocking), forwards fly concurrently; returns a
        FleetTicket for _resolve's merge — or None when the whole frame
        is local (caller keeps the untouched path)."""
        from ratelimiter_tpu.fleet.forwarder import FleetTicket

        local_pos, jobs = self._fleet_split(h64, ns, blob=blob,
                                            offsets=offsets,
                                            lengths=lengths)
        if local_pos is None and not jobs:
            return None
        lim = self._shard_limiters[shard]
        t = FleetTicket()
        t.b = int(h64.shape[0])
        t.limit = lim.config.limit
        t.t_sec = lim.clock.now()
        if local_pos is not None and local_pos.shape[0]:
            with self._locks[shard]:
                t.local = lim.launch_hashed(h64[local_pos], ns[local_pos])
            t.local_pos = local_pos
            t.t_sec = getattr(t.local, "t_sec", 0.0) or t.t_sec
        t.jobs = tuple(jobs)
        return t

    def _decide(self, shard: int, blob: bytes, offsets_b: bytes,
                lengths_b: bytes, ns_b: bytes, trace_id: int = 0):
        b = len(offsets_b) // 8
        lim = self._shard_limiters[shard]
        aud = audit.AUDITOR
        # Decision timestamp captured BEFORE the decide (the backend
        # reads its clock at launch; a post-decide read would lag by the
        # dispatch) — audit-off skips even this.
        t_dec = lim.clock.now() if aud is not None else 0.0
        try:
            if self._fast:
                h64, ns = self._hash_buffers(blob, offsets_b, lengths_b,
                                             ns_b)
                if self._fleet is not None:
                    local_pos, jobs = self._fleet_split(
                        h64, ns, blob=blob,
                        offsets=np.frombuffer(offsets_b, dtype=np.int64),
                        lengths=np.frombuffer(lengths_b, dtype=np.int64))
                    if local_pos is not None or jobs:
                        out = self._fleet_decide(shard, h64, ns,
                                                 local_pos, jobs)
                        if aud is not None:
                            aud.offer_hashed(h64, ns, t_dec, out,
                                             slice_idx=shard)
                        self._batch_hist.observe(float(b))
                        return self._pack_result(out)
                with self._locks[shard]:
                    out = lim.allow_hashed(h64, ns)
                # Live accuracy tap (ADR-016): h64 is the finalized
                # string hash (prefix already applied by the C++ blob
                # builder), so the hashed offer is exact; off = one
                # None check.
                if aud is not None:
                    aud.offer_hashed(h64, ns, t_dec, out,
                                     slice_idx=shard)
            else:
                offsets = np.frombuffer(offsets_b, dtype=np.int64)
                lengths = np.frombuffer(lengths_b, dtype=np.int64)
                ns = np.frombuffer(ns_b, dtype=np.int64)
                keys = [blob[o:o + l].decode("utf-8")
                        for o, l in zip(offsets.tolist(), lengths.tolist())]
                with self._locks[shard]:
                    out = lim.allow_batch(keys, ns.tolist())
                if aud is not None:
                    aud.offer_keys(keys, ns, t_dec, out,
                                   slice_idx=shard)
        except (InvalidNError, InvalidKeyError) as exc:
            raise _BridgeError(p.code_for(exc), str(exc)) from exc
        except Exception as exc:
            raise _BridgeError(p.code_for(exc), str(exc)) from exc
        self._batch_hist.observe(float(b))
        return self._pack_result(out)

    def _decide_hashed(self, shard: int, ids_b: bytes, ns_b: bytes,
                       trace_id: int = 0):
        """Hashed-lane blocking decide: the buffers are already finalized
        u64 hashes (C++ splitmix64) — frombuffer views go straight into
        allow_hashed's staging memcpy; zero host hash math."""
        b = len(ids_b) // 8
        lim = self._shard_limiters[shard]
        aud = audit.AUDITOR
        t_dec = lim.clock.now() if aud is not None else 0.0
        try:
            h64 = np.frombuffer(ids_b, dtype=np.uint64)
            ns = np.frombuffer(ns_b, dtype=np.int64)
            if self._fleet is not None:
                # Hashed-lane ids arrive FINALIZED (C++ splitmix64);
                # foreign rows forward via the inverse (bit-identical
                # at the owner — the forward_jobs columnar lane).
                local_pos, jobs = self._fleet_split(h64, ns)
                if local_pos is not None or jobs:
                    out = self._fleet_decide(shard, h64, ns, local_pos,
                                             jobs)
                    if aud is not None:
                        aud.offer_hashed(h64, ns, t_dec, out,
                                         slice_idx=shard)
                    self._batch_hist.observe(float(b))
                    return self._pack_result(out)
            with self._locks[shard]:
                out = lim.allow_hashed(h64, ns)
        except Exception as exc:
            raise _BridgeError(p.code_for(exc), str(exc)) from exc
        # Tap (ADR-016): the C++ io thread already ran splitmix64, so
        # these ARE finalized hashes (offer_hashed, not offer_ids). The
        # frombuffer view pins the bytes object — no copy.
        if aud is not None:
            aud.offer_hashed(h64, ns, t_dec, out, slice_idx=shard)
        self._batch_hist.observe(float(b))
        return self._pack_result(out)

    @staticmethod
    def _trace_enter(shard: int, trace_id: int) -> int:
        """First line of both launch callbacks. Recorder on: the entry
        stamp and the thread's trace context, which the spans below the
        door attribute themselves to. Nothing resets the context: the
        C++ dispatcher thread runs only these callbacks, and each sets
        it anew. Recorder off: one None check."""
        if tracing.RECORDER is None:
            return 0
        tracing.set_current(trace_id, shard)
        return tracing.now()

    @staticmethod
    def _trace_leave(ticket, t_enter: int, t_descend: int = 0):
        """Last line of both launch callbacks: the ticket carries the
        callback's stamps to _spans — its first line, where "descend"
        begins (the first line again, or where the string lane's "hash"
        closed) and its last line."""
        if t_enter:
            ticket.t_door = (t_enter, t_descend or t_enter, tracing.now())
        return ticket

    def _launch_hashed_cb(self, shard: int, ids_b: bytes, ns_b: bytes,
                          trace_id: int = 0):
        """Hashed-lane launch phase (pipelined): stage + enqueue without
        blocking; resolves through the same _resolve completer path."""
        t_enter = self._trace_enter(shard, trace_id)
        t0 = time.perf_counter()
        lim = self._shard_limiters[shard]
        try:
            h64 = np.frombuffer(ids_b, dtype=np.uint64)
            ns = np.frombuffer(ns_b, dtype=np.int64)
            if self._fleet is not None:
                ticket = self._fleet_launch(shard, h64, ns)
                if ticket is not None:
                    ticket.trace_id = trace_id
                    if audit.AUDITOR is not None:
                        ticket.audit = (h64, ns)
                    with self._depth_lock:
                        self._depth += 1
                        self._inflight_gauge.set(float(self._depth))
                    self._launch_hist.observe(time.perf_counter() - t0)
                    return self._trace_leave(ticket, t_enter)
            with self._locks[shard]:
                ticket = lim.launch_hashed(h64, ns)
        except Exception as exc:
            raise _BridgeError(p.code_for(exc), str(exc)) from exc
        ticket.trace_id = trace_id
        if audit.AUDITOR is not None:
            # Pin the frame's hashes to the ticket so _resolve can tap
            # (ADR-016); the frombuffer views keep the bytes alive.
            ticket.audit = (h64, ns)
        with self._depth_lock:
            self._depth += 1
            self._inflight_gauge.set(float(self._depth))
        self._launch_hist.observe(time.perf_counter() - t0)
        return self._trace_leave(ticket, t_enter)

    def _launch(self, shard: int, blob: bytes, offsets_b: bytes,
                lengths_b: bytes, ns_b: bytes, trace_id: int = 0):
        """Launch phase (pipelined hot path): hash + stage + enqueue the
        jitted step WITHOUT blocking on the device; the returned ticket
        is opaque to C++ and comes back through _resolve on the
        completer thread."""
        t_enter = self._trace_enter(shard, trace_id)
        t0 = time.perf_counter()
        lim = self._shard_limiters[shard]
        try:
            with tracing.span("hash", batch=len(offsets_b) // 8) as sp:
                h64, ns = self._hash_buffers(blob, offsets_b, lengths_b,
                                             ns_b)
            t_descend = sp.t_close
            if self._fleet is not None:
                ticket = self._fleet_launch(
                    shard, h64, ns, blob=blob,
                    offsets=np.frombuffer(offsets_b, dtype=np.int64),
                    lengths=np.frombuffer(lengths_b, dtype=np.int64))
                if ticket is not None:
                    ticket.trace_id = trace_id
                    if audit.AUDITOR is not None:
                        ticket.audit = (h64, ns)
                    with self._depth_lock:
                        self._depth += 1
                        self._inflight_gauge.set(float(self._depth))
                    self._launch_hist.observe(time.perf_counter() - t0)
                    return self._trace_leave(ticket, t_enter, t_descend)
            with self._locks[shard]:
                ticket = lim.launch_hashed(h64, ns)
        except Exception as exc:
            raise _BridgeError(p.code_for(exc), str(exc)) from exc
        ticket.trace_id = trace_id
        if audit.AUDITOR is not None:
            ticket.audit = (h64, ns)
        with self._depth_lock:
            self._depth += 1
            self._inflight_gauge.set(float(self._depth))
        self._launch_hist.observe(time.perf_counter() - t0)
        return self._trace_leave(ticket, t_enter, t_descend)

    def _fleet_resolve(self, shard: int, ticket):
        """Resolve one ticket, merging fleet tickets (local sub-resolve
        + in-flight forwards scattered back to frame order); plain
        tickets pass straight through to the shard limiter."""
        from ratelimiter_tpu.fleet.forwarder import (
            FleetTicket,
            collect_jobs,
            scatter_merge,
        )

        lim = self._shard_limiters[shard]
        if not isinstance(ticket, FleetTicket):
            return lim.resolve(ticket)
        parts = []
        err = None
        if ticket.local is not None:
            try:
                parts.append((ticket.local_pos, lim.resolve(ticket.local)))
            except Exception as exc:  # noqa: BLE001 — drain forwards first
                err = exc
        fparts, ferr = collect_jobs(self._fleet, ticket.jobs, lim.config,
                                    ticket.t_sec or lim.clock.now())
        parts.extend(fparts)
        err = err if err is not None else ferr
        if err is not None:
            raise err
        return scatter_merge(ticket.b, ticket.limit, parts)

    def _resolve(self, shard: int, ticket):
        """Resolve phase: block on the oldest in-flight dispatch (GIL
        released while the device drains) and hand the flat result
        buffers back to the C++ responder."""
        t0 = time.perf_counter()
        lim = self._shard_limiters[shard]
        self._door_ns[shard] = (ticket.t_door, ticket.t_lane)
        try:
            out = self._fleet_resolve(shard, ticket)
        except Exception as exc:
            raise _BridgeError(p.code_for(exc), str(exc)) from exc
        finally:
            with self._depth_lock:
                self._depth -= 1
                self._inflight_gauge.set(float(self._depth))
        aud = audit.AUDITOR
        if aud is not None and ticket.audit is not None:
            # Tap on the completer thread (ADR-016): shard-resolve order
            # is launch order, so the shadow oracle sees each shard's
            # (and thus each key's) timeline in decision order. The
            # timestamp is the ticket's LAUNCH-time now — the one the
            # sketch decided with — not resolve time: under a deep
            # in-flight window the skew would otherwise span sub-window
            # boundaries and read as tap-induced false denies.
            h64, ns = ticket.audit
            aud.offer_hashed(h64, ns,
                             getattr(ticket, "t_sec", 0.0)
                             or lim.clock.now(),
                             out, slice_idx=shard)
        self._resolve_hist.observe(time.perf_counter() - t0)
        self._batch_hist.observe(float(len(out)))
        return self._pack_result(out)

    def _reset(self, shard: int, key_bytes: bytes) -> None:
        try:
            self._shard_limiters[shard].reset(key_bytes.decode("utf-8"))
        except Exception as exc:
            raise _BridgeError(p.code_for(exc), str(exc)) from exc

    def _metrics(self) -> bytes:
        return self.registry.render().encode()

    def _dcn(self, payload: bytes) -> None:
        """T_DCN_PUSH receive path: merge the foreign payload into EVERY
        shard limiter (see dcn_peer.merge_push_payload for why that is
        double-count-free). The replay guard rejects stale/duplicate
        sequenced envelopes before any mass merges."""
        from ratelimiter_tpu.serving.dcn_peer import merge_push_payload

        try:
            merge_push_payload(
                self._shard_limiters, payload, self.dcn_secret,
                self._dcn_guard, self._fleet_announce,
                self.leases.on_gossip if self.leases is not None else None)
            if self.leases is not None:
                # An announce may have moved ownership: revoke grants
                # over ranges this member no longer owns (ADR-022).
                self.leases.check_epoch()
        except Exception as exc:
            raise _BridgeError(p.code_for(exc), str(exc)) from exc

    # ----------------------------------------------- key-routed side doors

    def shard_of(self, key: str) -> int:
        """Python mirror of the C++ FNV-1a shard router (server.cpp
        key_shard) — side doors (HTTP gateway, embedding) MUST route
        through this so a key's quota lives on one shard regardless of
        which surface served it."""
        n_shards = len(self._shard_limiters)
        if n_shards == 1:
            return 0
        # Constants copied bit-for-bit from server.cpp key_shard — the
        # basis there is nonstandard, and only C++<->Python AGREEMENT
        # matters (a mismatch silently gives one key two quotas).
        h = 1469598103934665603
        for b in key.encode("utf-8"):
            h ^= b
            h = (h * 1099511628211) & 0xFFFFFFFFFFFFFFFF
        return h % n_shards

    def shard_of_id(self, raw_id: int) -> int:
        """Python mirror of the C++ hashed-lane router (server.cpp
        T_ALLOW_HASHED parse): finalized splitmix64(id) mod shards."""
        n_shards = len(self._shard_limiters)
        if n_shards == 1:
            return 0
        from ratelimiter_tpu.ops.hashing import splitmix64

        return int(splitmix64(np.asarray([raw_id], np.uint64))[0] % n_shards)

    def decide_one(self, key: str, n: int = 1, *, trace_id: int = 0,
                   deadline=None):
        """Single-key decision routed to the key's dispatch shard — the
        HTTP/gRPC gateways' decide callable when this server fronts
        traffic. Observability covers every shard when the server was
        built with ``shard_decorate`` (the server binary does this).

        Each call is one synchronous batch-of-1 dispatch serialized with
        the shard's wire batches — fine for the interop surfaces these
        gateways exist for (curl, sidecars, admin); bulk traffic belongs
        on the binary protocol, whose micro-batching this path cannot
        join (the C++ batcher owns the coalescing window).

        ``trace_id`` (ADR-014): a sampled gateway request (HTTP
        ``traceparent`` / gRPC metadata) records its synchronous device
        dispatch into the flight recorder under the owning shard.

        ``deadline`` (ADR-015, RELATIVE seconds of budget): an already-
        expired budget is shed — answered per the limiter's
        fail-open/fail-closed policy without a dispatch (this path is
        synchronous, so arrival is the only shed point)."""
        if deadline is not None and float(deadline) <= 0.0:
            from ratelimiter_tpu.core.errors import DeadlineExceededError
            from ratelimiter_tpu.core.types import fail_open_result

            cfg = self.limiter.config
            if cfg.fail_open:
                return fail_open_result(
                    cfg.limit,
                    self.limiter.clock.now() + float(cfg.window))
            raise DeadlineExceededError(
                "request deadline expired before dispatch")
        if self._fleet is not None:
            res = self._fleet_decide_one(key, n)
            if res is not None:
                return res
        shard = self.shard_of(key)
        rec = tracing.RECORDER
        aud = audit.AUDITOR
        t_dec = (self._shard_limiters[shard].clock.now()
                 if aud is not None else 0.0)
        t0 = tracing.now() if rec is not None else 0
        with self._locks[shard]:
            res = self._shard_limiters[shard].allow_n(key, n)
        if rec is not None:
            rec.record("device", t0, tracing.now(), trace_id=trace_id,
                       shard=shard)
        if aud is not None:
            # HTTP/gRPC side-door decisions join the audit stream too
            # (ADR-016) — the worker normalizes the scalar Result.
            aud.offer_keys([key], [n], t_dec, res, slice_idx=shard)
        return res

    def _fleet_decide_one(self, key: str, n: int):
        """Scalar fleet routing for the gateway side doors: None =
        locally owned on live state (fall through to the shard path)."""
        from ratelimiter_tpu.core.errors import StorageUnavailableError
        from ratelimiter_tpu.core.types import fail_open_result

        core = self._fleet
        h64 = core.hash_keys([key])
        owner = int(core.owners_of_hash(h64)[0])
        if owner == core.self_ordinal:
            if core._adopted_buckets.any() and bool(
                    core._adopted_buckets[
                        int(core.map.bucket_of_hash(h64)[0])]):
                return core.adopted_submit(
                    lambda: core.adopted_unit.allow_n(key, n)).result()
            return None
        if not core.forward_enabled:
            raise core.redirect_error(int(h64[0]), owner)
        try:
            return core.forward_allow_n(owner, key, n).result(
                timeout=core.forward_deadline + 2.0)
        except Exception as exc:  # noqa: BLE001 — degrade per policy
            core.note_forward_failure(owner, exc, 1)
            cfg = self.limiter.config
            if not cfg.fail_open:
                raise StorageUnavailableError(
                    f"fleet forward failed ({exc}); fails closed per "
                    f"config") from exc
            return fail_open_result(
                cfg.limit, self.limiter.clock.now() + float(cfg.window))

    def reset_one(self, key: str) -> None:
        """Reset routed to the key's dispatch shard (resetting shard 0's
        limiter for a key owned by shard 2 would be a silent no-op) —
        or, under fleet, to the key's OWNING HOST (same rule one layer
        up: a local reset of a foreign key resets nothing)."""
        if self._fleet is not None:
            core = self._fleet
            h64 = core.hash_keys([key])
            owner = int(core.owners_of_hash(h64)[0])
            if owner != core.self_ordinal:
                if not core.forward_enabled:
                    raise core.redirect_error(int(h64[0]), owner)
                core.forward_op(owner, "reset", key).result(
                    timeout=core.forward_deadline + 2.0)
                return
            if core._adopted_buckets.any() and bool(
                    core._adopted_buckets[
                        int(core.map.bucket_of_hash(h64)[0])]):
                core.adopted_submit(
                    lambda: core.adopted_unit.reset(key)).result()
                return
        shard = self.shard_of(key)
        with self._locks[shard]:
            self._shard_limiters[shard].reset(key)

    def decide_many(self, pairs):
        """Bulk decide for the gRPC AllowBatch surface: group by owning
        shard, ONE allow_batch per touched shard (in-batch same-key
        sequencing preserved — a key's requests all land on its shard in
        frame order), results reassembled in request order. Under fleet,
        rows owned elsewhere route per key first (gRPC is an interop
        side door; bulk fleet traffic belongs on the binary lanes)."""
        pairs = list(pairs)
        if self._fleet is not None:
            core = self._fleet
            h64 = core.hash_keys([k for k, _ in pairs])
            owners = core.owners_of_hash(h64)
            if not core.all_local(owners):
                return [self.decide_one(k, n) for k, n in pairs]
        by_shard: dict = {}
        for i, (key, n) in enumerate(pairs):
            by_shard.setdefault(self.shard_of(key), []).append((i, key, n))
        results = [None] * len(pairs)
        for shard, items in by_shard.items():
            with self._locks[shard]:
                out = self._shard_limiters[shard].allow_batch(
                    [k for _, k, _ in items], [n for _, _, n in items])
            for (i, _, _), res in zip(items, out.results()):
                results[i] = res
        return results

    # ------------------------------------------------- dynamic config

    def set_shard_health(self, shard: int, quarantined: bool) -> None:
        """Mirror one shard's quarantine state into the C++ door (ABI
        10, ADR-015) — ``stats()["shard_quarantined"]`` then reports the
        degraded topology. Wire the quarantine manager's
        ``on_state_change`` to this."""
        self._server.set_shard_health(int(shard), bool(quarantined))

    def refresh_fail_open_params(self) -> None:
        """Push the live default limit/window into the C++ door's atomic
        fail-open stamp fields. Called by update_limit/update_window; the
        C++ side ALSO refreshes the LIMIT from every completed dispatch
        (so direct ``limiter.update_limit`` calls converge after the
        next decide), but the window only moves through this push."""
        from ratelimiter_tpu.observability.decorators import undecorated

        cfg = undecorated(self._shard_limiters[0]).config
        self._server.set_limits(int(cfg.limit), float(cfg.window))

    def update_limit(self, new_limit: int) -> None:
        """Dynamic limit change applied to EVERY shard limiter, then
        pushed to the C++ fail-open stamp — an SLO-breach fail-open
        response issued before any post-update dispatch completes still
        carries the new limit (ISSUE-3 bugfix satellite)."""
        for shard, lim in enumerate(self._shard_limiters):
            with self._locks[shard]:
                lim.update_limit(new_limit)
        self.refresh_fail_open_params()

    def update_window(self, new_window: float) -> None:
        """Dynamic window change, every shard + C++ stamp refresh."""
        for shard, lim in enumerate(self._shard_limiters):
            with self._locks[shard]:
                lim.update_window(new_window)
        self.refresh_fail_open_params()

    # ------------------------------------------------- policy management

    def set_override_all(self, key: str, limit=None, *,
                         window_scale: float = 1.0):
        """Apply an override on EVERY shard limiter: keys hash-route, so
        the owning shard must have it — and setting it everywhere is
        idempotent for the others (their copy is simply never queried for
        this key)."""
        ov = None
        for shard, lim in enumerate(self._shard_limiters):
            with self._locks[shard]:
                ov = lim.set_override(key, limit, window_scale=window_scale)
        unit = self._fleet.adopted_unit if self._fleet is not None else None
        if unit is not None:
            # Adopted-range keys decide on the standby unit — mirror the
            # write there too (write-all, one more unit).
            ov = self._fleet.adopted_submit(
                lambda: unit.set_override(
                    key, limit, window_scale=window_scale)).result()
        return ov

    def get_override_one(self, key: str):
        if self._fleet is not None and self._fleet.adopted_unit is not None:
            core = self._fleet
            h64 = core.hash_keys([key])
            if bool(core._adopted_buckets[
                    int(core.map.bucket_of_hash(h64)[0])]):
                # Overrides restored from the dead host's WAL live only
                # in the standby unit.
                unit = core.adopted_unit
                return core.adopted_submit(
                    lambda: unit.get_override(key)).result()
        shard = self.shard_of(key)
        with self._locks[shard]:
            return self._shard_limiters[shard].get_override(key)

    def delete_override_all(self, key: str) -> bool:
        existed = False
        for shard, lim in enumerate(self._shard_limiters):
            with self._locks[shard]:
                existed = lim.delete_override(key) or existed
        unit = self._fleet.adopted_unit if self._fleet is not None else None
        if unit is not None:
            existed = self._fleet.adopted_submit(
                lambda: unit.delete_override(key)).result() or existed
        return existed

    @property
    def shard_limiters(self):
        """All shard limiters (index 0 = the caller's). A DCN exporter
        must push from EVERY one of these — shard 0 alone misses
        (N-1)/N of local traffic."""
        return list(self._shard_limiters)

    # ------------------------------------------------------------ lifecycle

    def start(self) -> None:
        self.port = self._server.start(self.host, self.port)

    def shutdown(self, *, close_limiters: bool = True) -> None:
        """Stop the C++ door (drains in-flight work) and, by default,
        close the owned shard clones. Pass close_limiters=False when
        something must still read limiter state after the listener stops
        — the durability subsystem's final snapshot (serving/__main__.py)
        captures AFTER the last decision is answered, so a graceful
        shutdown loses nothing; call close_shards() afterwards."""
        self.registry.remove_collect_hook(self._collect_transport_metrics)
        self._server.shutdown()
        if close_limiters:
            self.close_shards()

    def close_shards(self) -> None:
        # Shards beyond the caller's limiter are owned here.
        for lim in self._shard_limiters[1:]:
            lim.close()

    def stats(self) -> dict:
        return self._server.stats()

    def transport_stats(self) -> dict:
        """Same envelope as RateLimitServer.transport_stats (ADR-025):
        the C++ io thread owns the counters; this is a snapshot read."""
        st = self._server.stats()
        sh = dict(st.get("shm", {}))
        # The native door does not sample live ring occupancy (the io
        # thread owns the rings); report 0 so the gauge set is uniform.
        sh.setdefault("req_ring_used_bytes", 0)
        sh.setdefault("rep_ring_used_bytes", 0)
        # Network-engine ledger (ISSUE-20, ADR-026): selected backend,
        # ring count, probe verdict and the syscall counters — rides
        # transport_stats so /healthz carries the probe record.
        return {"connections": dict(st.get("transport", {})), "shm": sh,
                "net": dict(st.get("net", {}))}

    def _collect_transport_metrics(self) -> None:
        st = self.transport_stats()
        g = self.registry.gauge(
            "rate_limiter_transport_connections",
            "Connections accepted per transport (cumulative)")
        for k, v in st["connections"].items():
            g.set(v, transport=k)
        sh = st["shm"]
        self.registry.gauge(
            "rate_limiter_shm_lanes_active",
            "Live shared-memory lanes (ADR-025)").set(sh["lanes_active"])
        self.registry.gauge(
            "rate_limiter_shm_doorbell_wakes",
            "eventfd wakeups taken by shm ring consumers").set(
                sh["doorbell_wakes"])
        self.registry.gauge(
            "rate_limiter_shm_spin_hits",
            "shm records claimed during the bounded spin (no syscall)"
        ).set(sh["spin_hits"])
        self.registry.gauge(
            "rate_limiter_shm_ring_full_stalls",
            "shm ring-full backpressure stalls").set(
                sh["ring_full_stalls"])
        rg = self.registry.gauge(
            "rate_limiter_shm_records",
            "Frames carried over shm rings, by direction")
        rg.set(sh["records_in"], direction="in")
        rg.set(sh["records_out"], direction="out")
        ug = self.registry.gauge(
            "rate_limiter_shm_ring_used_bytes",
            "Current shm ring occupancy, summed over lanes")
        ug.set(sh["req_ring_used_bytes"], ring="req")
        ug.set(sh["rep_ring_used_bytes"], ring="rep")
        hg = self.registry.gauge(
            "rate_limiter_shm_ring_highwater_bytes",
            "High-water shm ring occupancy across lanes")
        hg.set(sh["req_ring_highwater_bytes"], ring="req")
        hg.set(sh["rep_ring_highwater_bytes"], ring="rep")
        net = st.get("net", {})
        if net:
            self.registry.gauge(
                "rate_limiter_net_engine_info",
                "Network engine identity (value 1): labels engine "
                "(epoll/uring), rings, probe (pass/fail/off)").set(
                    1, engine=net.get("engine", "epoll"),
                    rings=str(net.get("rings", 0)),
                    probe=net.get("uring_probe", "off"))
            sg = self.registry.gauge(
                "rate_limiter_net_syscalls_total",
                "Wire-loop syscalls by kind (recv/writev/wait/wake) — "
                "divide by decisions_total for syscalls per decision")
            sg.set(net.get("recv_calls", 0), kind="recv")
            sg.set(net.get("writev_calls", 0), kind="writev")
            sg.set(net.get("wait_calls", 0), kind="wait")
            sg.set(net.get("wake_calls", 0), kind="wake")
            self.registry.gauge(
                "rate_limiter_net_writev_frames",
                "Reply frames flushed through vectored writes — over "
                "net_syscalls_total{kind=\"writev\"} this is the "
                "reply batch factor").set(net.get("writev_frames", 0))
        # The door's own stage sums (ABI 9): atomics the C++ completer
        # adds the stamps of EVERY dispatch to (the ones _spans is
        # handed), recorder on or off — what a stage costs in an
        # untraced run, and the exact twin of the ring-derived
        # rate_limiter_stage_seconds.
        door = self.stats()
        stage = door["stage_ns"]
        tg = self.registry.gauge(
            "rate_limiter_door_stage_seconds_total",
            "Wall time the native door's pipeline stages have consumed "
            "(cumulative, always on): io = enqueue to drain, dispatch = "
            "drain to ticket pushed, device = blocked on the oldest "
            "dispatch, complete = resolve returned to replies handed "
            "over; over door_dispatches_total the exact mean per "
            "dispatch")
        for name in ("io", "dispatch", "device", "complete"):
            tg.set(stage[name] / 1e9, stage=name)
        # What the door's threads wait for (ADR-014 addendum): wall time
        # per state, C++ atomics added to where the waiting happens,
        # recorder on or off. The states of a thread tile its loop, so a
        # thread's states sum to its wall since start (summed over
        # dispatch units: four dispatchers sum to four walls).
        sg = self.registry.gauge(
            "rate_limiter_door_thread_seconds_total",
            "Wall time the native door's dispatcher and completer "
            "threads have spent in each state (cumulative, always on, "
            "summed over dispatch units): idle = nothing to drain / "
            "nothing in flight, gather = drain to the call for the GIL, "
            "gil = inside PyGILState_Ensure, python = GIL taken to GIL "
            "released (the launch / resolve callback), slot = waiting "
            "for an in-flight slot, other = the rest of the loop; a "
            "thread's states sum to its wall")
        for thread, states in door["thread_ns"].items():
            for state, ns in states.items():
                sg.set(ns / 1e9, thread=thread, state=state)
        cg = self.registry.gauge(
            "rate_limiter_door_thread_cpu_seconds_total",
            "CPU time of the native door's threads by role (cumulative; "
            "each thread's CPU clock read at scrape): over the thread's "
            "states' sum, the share of its time it computes and does "
            "not wait")
        for thread, ns in door["thread_cpu_ns"].items():
            cg.set(ns / 1e9, thread=thread)
        self.registry.gauge(
            "rate_limiter_door_dispatches_total",
            "Batched dispatches the native door has completed "
            "(cumulative)").set(stage["batches"])
        # What coalescing adds, over the same dispatches: frames_total /
        # dispatches_total is the frames a dispatch took.
        self.registry.gauge(
            "rate_limiter_door_frames_total",
            "Queued requests the native door's coalescer has drained "
            "into completed dispatches (cumulative): a wire frame, or "
            "the part of one a dispatch took — a frame cut at a "
            "dispatch's row cap counts once in each dispatch that took "
            "a part of it").set(stage["frames"])
        self.registry.gauge(
            "rate_limiter_door_carved_frames_total",
            "Frames the native door's coalescer has cut at a dispatch's "
            "row cap (--max-batch; 16,384 rows where it is not given; "
            "cumulative): the head filled one dispatch, the "
            "rest opened the next, the reply still goes out as one "
            "frame").set(stage["carved"])
