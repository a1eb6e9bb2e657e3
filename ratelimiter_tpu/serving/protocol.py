"""Wire protocol for the rate-limit service.

The reference plans a gRPC ``Allow/AllowN/Reset`` service plus health
(``docs/ARCHITECTURE.md:287-304``, stub ``cmd/server/main.go:13-17``). No
gRPC runtime ships in this environment, so the service speaks an
equivalent compact binary protocol over TCP — same RPC surface, same
semantics, pipelinable (requests carry ids; responses may arrive out of
order, which is what lets the server micro-batch across in-flight
requests from every connection).

Frame layout (little-endian):

    u32  payload_length          (not counting these 4 bytes)
    u8   type
    u64  request_id              (echoed in the response)
    ...  type-specific body

Requests:
    ALLOW_N     (1): u32 n, u16 key_len, key utf-8
    RESET       (2): u16 key_len, key utf-8
    HEALTH      (3): -
    METRICS     (4): -
    ALLOW_BATCH (5): u32 count, then count x {u32 n, u16 key_len, key} —
                     one frame, many decisions (the client-side batching
                     analog of Redis pipelining; decisions still coalesce
                     with every other connection in the micro-batcher)
    ALLOW_HASHED (11): u32 count | u64 ids[count] | u32 ns[count] —
                     the zero-copy bulk lane (ADR-011): COLUMNAR raw
                     u64 key ids, parsed as np.frombuffer views and
                     staged with one memcpy; splitmix64 + the (h1, h2)
                     split run on device inside the jitted step. Only
                     sketch-family backends serve it (E_INVALID_CONFIG
                     elsewhere). The id keyspace is disjoint from the
                     string-key space; RESET/POLICY address string keys
                     only.
    POLICY_SET  (7): u8 flags (bit0 has_limit), i64 limit,
                     f64 window_scale, u16 key_len, key utf-8 —
                     tiered per-key override (policy engine)
    POLICY_GET  (8): u16 key_len, key utf-8
    POLICY_DEL  (9): u16 key_len, key utf-8
    SNAPSHOT   (10): - — trigger a durability snapshot now
                     (persistence/); E_INVALID_CONFIG when the server
                     runs without --snapshot-dir. Asyncio front door
                     only (same asymmetry as POLICY_*): the native C++
                     door answers unknown-type and manages snapshots
                     over HTTP POST /v1/snapshot instead

Responses:
    RESULT   (129): u8 flags (bit0 allowed, bit1 fail_open), i64 limit,
                    i64 remaining, f64 retry_after, f64 reset_at
    OK       (130): -
    HEALTH   (131): u8 status (1 serving, 0 draining), f64 uptime_s,
                    u64 decisions_total
    METRICS  (132): u32 text_len, prometheus text utf-8
    RESULT_BATCH (133): i64 limit, u32 count, then count x {u8 flags,
                    i64 remaining, f64 retry_after, f64 reset_at}.
                    NOTE: the header ``limit`` is the DEFAULT limit;
                    overridden keys' true limits ride the scalar RESULT
                    path and every HTTP/gRPC surface (wire-format
                    stability with the native front door).
    POLICY   (134): u8 found, i64 limit, f64 window_scale — answer to
                    POLICY_SET (the stored entry) and POLICY_GET
                    (found=0 means default tier); POLICY_DEL answers it
                    too (found=1 iff an override existed)
    SNAPSHOT (135): u64 snapshot_id, u64 wal_seq (the watermark the
                    snapshot captured), f64 duration_s
    RESULT_HASHED (136): u8 batch_flags (bit1 fail_open, whole-batch),
                    i64 limit (the DEFAULT limit, as in RESULT_BATCH),
                    u32 count, u8 allowed_bits[ceil(count/8)]
                    (little-endian bit order), then COLUMNAR
                    i64 remaining[count] | f64 retry[count] |
                    f64 reset[count]. The response shape resolve
                    packs directly (core/types.wire_pack): the
                    server's encode is slice memcpys, the client's
                    parse is np.frombuffer views.
    ERROR    (255): u16 code, u16 msg_len, msg utf-8; for ALLOW_BATCH an
                    error response covers the whole frame

Error codes mirror the error sentinels (core/errors.py; reference
``errors.go:5-20``) so clients can re-raise the right exception type.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass
from typing import Tuple

from ratelimiter_tpu.core.errors import (
    ClosedError,
    DeadlineExceededError,
    InvalidConfigError,
    InvalidKeyError,
    InvalidNError,
    NotOwnerError,
    RateLimiterError,
    StorageUnavailableError,
)
from ratelimiter_tpu.core.types import Result

MAX_FRAME = 1 << 20  # 1 MiB: far above any legal request, bounds bad input
#: DCN push frames carry whole slabs / debt deltas (d x w counters), so
#: they get their own, larger bound. d=8 w=2^20 int64 is 64 MiB.
MAX_DCN_FRAME = 96 << 20
MAX_KEY_LEN = 4096

# Request types
T_ALLOW_N = 1
T_RESET = 2
T_HEALTH = 3
T_METRICS = 4
T_ALLOW_BATCH = 5
T_DCN_PUSH = 6
T_POLICY_SET = 7
T_POLICY_GET = 8
T_POLICY_DEL = 9
T_SNAPSHOT = 10
T_ALLOW_HASHED = 11
#: Fleet ownership map fetch (ADR-017): empty body; answers
#: T_FLEET_MAP_R with the server's current map (JSON — control plane).
#: E_INVALID_CONFIG on non-fleet servers; asyncio front door only (the
#: native C++ door answers unknown-type — fetch the map from an asyncio
#: member, the fleet config file, or the HTTP /healthz fleet block).
T_FLEET_MAP = 12
#: Client-embedded quota leases (ADR-022): a client asks for a bounded
#: token budget on one hot key (GRANT), tops it up / reports local
#: consumption (RENEW), and hands the remainder back (RETURN). All
#: three answer T_LEASE_R. 13..15 are the LAST base-type slots below
#: FORWARD_FLAG (0x10) — any later request family needs a sub-typed
#: frame, not a new type byte.
T_LEASE_GRANT = 13
T_LEASE_RENEW = 14
T_LEASE_RETURN = 15
#: Shared-memory lane negotiation (ADR-025). Type byte 16 is the one
#: deliberate exception to the "13..15 are the last base slots" rule:
#: 16 == FORWARD_FLAG with base type 0, and base type 0 is not a valid
#: request, so an EXACT match on the raw (unstripped) type byte is
#: unambiguous. Both doors and split_forward() special-case the exact
#: value BEFORE any flag stripping; T_SHM_HELLO never composes with the
#: trace/deadline/forward extensions. Body: u32 version | u32
#: req_ring_bytes | u32 rep_ring_bytes (0 = server default). Servers
#: with --shm off answer T_ERROR E_INVALID_CONFIG, keeping the off-path
#: wire byte-identical for clients that never send the hello.
T_SHM_HELLO = 16

# DCN payload kinds (parallel/dcn.py exchange families)
DCN_KIND_SLABS = 1   # windowed: completed sub-window slabs
DCN_KIND_DEBT = 2    # token bucket: accumulated debt delta
#: Fleet announce/heartbeat (ADR-017): u32 len + JSON payload carrying
#: the sender's id, liveness stamp and its view of the ownership map
#: (epoch + host ranges). Rides T_DCN_PUSH so it inherits the RLA2
#: HMAC + replay-guard envelope (ADR-007) on both front doors — an
#: unauthenticated announce on a secret-bearing server is rejected
#: before it can move ownership.
DCN_KIND_FLEET = 3
#: Lease revocation gossip (ADR-022): u32 len + JSON payload naming the
#: revoked scope (one hashed key token or "all"), the reason and the
#: sender's epoch. Rides T_DCN_PUSH so member→member revocations
#: inherit the RLA2 HMAC + replay-guard envelope — an unauthenticated
#: push on a secret-bearing server cannot revoke (or suppress) leases.
DCN_KIND_LEASE = 4
# Response types
T_RESULT = 129
T_OK = 130
T_HEALTH_R = 131
T_METRICS_R = 132
T_RESULT_BATCH = 133
T_POLICY_R = 134
T_SNAPSHOT_R = 135
T_RESULT_HASHED = 136
T_FLEET_MAP_R = 137
#: Answer to every T_LEASE_* request (ADR-022).
T_LEASE_R = 138
#: Unsolicited server→client lease revocation push (ADR-022): sent with
#: req_id=0 on the connection that granted, so clients must tolerate
#: rid-0 frames on a lease-bearing connection (both client read loops
#: consume them before request/response correlation).
T_LEASE_REVOKE = 139
#: Answer to T_SHM_HELLO (ADR-025): u8 ok | u32 req_cap | u32 rep_cap |
#: u16 path_len + shm path | u16 path_len + control-socket path. 140 is
#: left free to keep the lease family (138/139) contiguous with any
#: future lease response.
T_SHM_HELLO_R = 141
T_ERROR = 255

# --------------------------------------------- trace context (ADR-014)
#
# Optional caller trace propagation: setting bit 6 (0x40) on any REQUEST
# type byte means the body is prefixed with a u64 trace id (little-
# endian). Request types are 1..11 and response types >= 128, so the
# flagged range 0x41..0x4B collides with nothing; responses never carry
# the flag (the request id already correlates them). Servers that
# predate the flag drop the connection on the unknown type — the flag is
# only sent by callers that opted into tracing against a known server.
# For T_DCN_PUSH the trace id rides OUTSIDE the HMAC envelope (the
# envelope wraps the body; the trace prefix is framing), so sampled DCN
# pushes need no key rotation and verification is unchanged.
TRACE_FLAG = 0x40
_TRACE_ID = struct.Struct("<Q")

# ------------------------------------------- deadline context (ADR-015)
#
# Request deadline propagation, the same frame-extension mechanism as
# the trace id: bit 5 (0x20) on a REQUEST type byte means the body is
# prefixed with an f64 RELATIVE deadline budget in seconds (relative,
# not absolute — client and server wall clocks need not agree; the
# receiver anchors the budget to frame arrival). Servers SHED work
# whose budget has expired before its dispatch runs, answering per the
# fail-open/fail-closed policy instead of burning a dispatch slot
# (core/errors.DeadlineExceededError on the fail-closed side). When
# both extensions are present the trace id comes FIRST on the wire:
# apply ``with_deadline`` before ``with_trace``. For T_DCN_PUSH the
# prefix rides OUTSIDE the HMAC envelope, exactly like the trace id.
DEADLINE_FLAG = 0x20
_DEADLINE = struct.Struct("<d")
_REQ_FLAGS = TRACE_FLAG | DEADLINE_FLAG

# ------------------------------------------- forward hint (ADR-019)
#
# Bit 4 (0x10) on a REQUEST type byte marks a fleet forward-lane frame:
# a coalesced window of rows that are ALL owned by the receiving host
# (the sender routed them). It carries no body prefix — it is a pure
# dispatch hint: the receiver's batcher must dispatch the frame
# STANDALONE, never coalesced into a window that also holds client
# rows needing onward forwarding. Coalescing the two couples this
# reply to the receiver's own forward legs, and under symmetric mixed
# fleet traffic that dependency chain extends without bound (each
# reply waits on legs of a window formed later — second-long tails,
# and outright forward-deadline expiry at 4 hosts). Misuse by an
# ordinary client is harmless: the hint only steers batching. Applied
# OUTERMOST (after with_deadline / before nothing): with_forward sets
# only the bit.
FORWARD_FLAG = 0x10


def with_forward(frame: bytes) -> bytes:
    """Mark a request frame as a fleet forward-lane window (dispatch
    hint; no body change). Apply LAST — after with_deadline/with_trace."""
    length, type_, req_id = _HDR.unpack_from(frame)
    if type_ & FORWARD_FLAG or type_ >= 128:
        raise ProtocolError(f"type {type_} cannot carry the forward hint")
    return (_HDR.pack(length, type_ | FORWARD_FLAG, req_id)
            + frame[HEADER_SIZE:])


def split_forward(type_: int):
    """(base_type, is_forward) — strip the forward hint bit. Call AFTER
    split_request (the hint is a bare bit, the other extensions carry
    body prefixes). T_SHM_HELLO (16 == FORWARD_FLAG | 0) is exempt —
    the doors intercept it on the raw byte before any stripping, and
    this guard keeps late callers from mangling it into base type 0."""
    if type_ != T_SHM_HELLO and type_ < 128 and type_ & FORWARD_FLAG:
        return type_ & ~FORWARD_FLAG, True
    return type_, False


def with_deadline(frame: bytes, budget_s: float) -> bytes:
    """Re-frame a request with the deadline extension (flag bit on the
    type byte + f64 relative budget prefixed to the body). Must be
    applied BEFORE ``with_trace`` — the trace id is the outermost
    prefix on the wire."""
    length, type_, req_id = _HDR.unpack_from(frame)
    if type_ & _REQ_FLAGS or type_ >= 128:
        raise ProtocolError(f"type {type_} cannot carry a deadline")
    body = _DEADLINE.pack(float(budget_s)) + frame[HEADER_SIZE:]
    return _HDR.pack(1 + 8 + len(body), type_ | DEADLINE_FLAG,
                     req_id) + body


def with_trace(frame: bytes, trace_id: int) -> bytes:
    """Re-frame a request with the trace-id extension (flag bit on the
    type byte + u64 id prefixed to the body). Composes with the
    deadline extension (apply ``with_deadline`` first; the trace id
    ends up outermost)."""
    length, type_, req_id = _HDR.unpack_from(frame)
    if type_ & TRACE_FLAG or type_ >= 128:
        raise ProtocolError(f"type {type_} cannot carry a trace id")
    body = _TRACE_ID.pack(trace_id & 0xFFFFFFFFFFFFFFFF) \
        + frame[HEADER_SIZE:]
    return _HDR.pack(1 + 8 + len(body), type_ | TRACE_FLAG, req_id) + body


def split_trace(type_: int, body: bytes):
    """(base_type, trace_id, body) from a possibly-flagged request frame
    — servers call this once per frame; unflagged frames pass through
    with trace_id 0 and zero copies. The deadline flag (if any) stays
    on the returned type for ``split_request`` callers."""
    if not (type_ & TRACE_FLAG) or type_ >= 128:
        return type_, 0, body
    if len(body) < _TRACE_ID.size:
        raise ProtocolError("short trace-id extension")
    (trace_id,) = _TRACE_ID.unpack_from(body)
    return type_ & ~TRACE_FLAG, trace_id, body[_TRACE_ID.size:]


def split_request(type_: int, body: bytes):
    """(base_type, trace_id, deadline_budget_s, body) — strips BOTH
    frame extensions in canonical order (trace id, then deadline).
    Unflagged frames pass through with (0, None) and zero copies.
    ``deadline_budget_s`` is the sender's RELATIVE budget (None = no
    deadline; <= 0 = already expired on arrival); anchor it to frame
    arrival on the receiving side."""
    type_, trace_id, body = split_trace(type_, body)
    if not (type_ & DEADLINE_FLAG) or type_ >= 128:
        return type_, trace_id, None, body
    if len(body) < _DEADLINE.size:
        raise ProtocolError("short deadline extension")
    (budget,) = _DEADLINE.unpack_from(body)
    return (type_ & ~DEADLINE_FLAG, trace_id, budget,
            body[_DEADLINE.size:])


# Error codes <-> exceptions (reference errors.go:5-20 analogs)
E_INVALID_N = 1
E_INVALID_KEY = 2
E_STORAGE_UNAVAILABLE = 3
E_CLOSED = 4
E_INVALID_CONFIG = 5
E_SHUTTING_DOWN = 6
E_INTERNAL = 7
#: The request's propagated deadline expired before its dispatch ran
#: (fail-closed side of deadline shedding, ADR-015).
E_DEADLINE = 8
#: Fleet typed redirect (ADR-017): the answering server does not own the
#: frame's hash buckets under its ownership epoch and forwarding is off.
#: The message is parse_not_owner-parseable (owner address + epoch), so
#: stale routers re-route instead of retrying the wrong host.
E_NOT_OWNER = 9

_CODE_TO_EXC = {
    E_INVALID_N: InvalidNError,
    E_INVALID_KEY: InvalidKeyError,
    E_STORAGE_UNAVAILABLE: StorageUnavailableError,
    E_CLOSED: ClosedError,
    E_INVALID_CONFIG: InvalidConfigError,
    E_SHUTTING_DOWN: StorageUnavailableError,
    E_INTERNAL: RateLimiterError,
    E_DEADLINE: DeadlineExceededError,
    E_NOT_OWNER: NotOwnerError,
}


def code_for(exc: Exception) -> int:
    if isinstance(exc, NotOwnerError):
        return E_NOT_OWNER
    if isinstance(exc, DeadlineExceededError):
        return E_DEADLINE
    if isinstance(exc, InvalidNError):
        return E_INVALID_N
    if isinstance(exc, (InvalidKeyError, UnicodeDecodeError)):
        # Keys are UTF-8 on the wire; undecodable bytes are a bad KEY,
        # not a server fault (native front door answers E_INVALID_KEY
        # for the same frame — the two servers must agree).
        return E_INVALID_KEY
    if isinstance(exc, StorageUnavailableError):
        return E_STORAGE_UNAVAILABLE
    if isinstance(exc, ClosedError):
        return E_CLOSED
    if isinstance(exc, InvalidConfigError):
        return E_INVALID_CONFIG
    return E_INTERNAL


def exception_for(code: int, msg: str) -> Exception:
    if code == E_NOT_OWNER:
        info = parse_not_owner(msg) or {}
        return NotOwnerError(msg, owner=info.get("owner", ""),
                             epoch=info.get("epoch", 0))
    return _CODE_TO_EXC.get(code, RateLimiterError)(msg)


# ------------------------------------------------ fleet frames (ADR-017)
#
# Fleet control-plane payloads are JSON: the ownership map is small,
# changes rarely, and operators read it straight off /healthz — binary
# framing would buy nothing. Decision traffic NEVER rides these frames
# (mis-routed rows forward over the plain string/hashed decision lanes,
# so both doors parse them natively).

def format_not_owner(bucket: int, owner: str, epoch: int,
                     buckets: int) -> str:
    """The E_NOT_OWNER message contract: stable ``k=v`` tokens so
    clients re-route without a side channel. ``owner`` is ``host:port``
    (or ``id@host:port``)."""
    return (f"not owner: bucket={bucket} owner={owner} "
            f"epoch={epoch} buckets={buckets}")


def parse_not_owner(msg: str):
    """-> {"bucket", "owner", "epoch", "buckets"} or None if the message
    does not carry the redirect contract."""
    if not msg.startswith("not owner:"):
        return None
    out = {}
    for tok in msg.split():
        if "=" not in tok:
            continue
        k, _, v = tok.partition("=")
        if k in ("bucket", "epoch", "buckets"):
            try:
                out[k] = int(v)
            except ValueError:
                return None
        elif k == "owner":
            out[k] = v
    if "owner" not in out or "epoch" not in out:
        return None
    return out


def encode_fleet_map(req_id: int) -> bytes:
    return encode_simple(T_FLEET_MAP, req_id)


def encode_fleet_map_r(req_id: int, payload: dict) -> bytes:
    import json

    jb = json.dumps(payload, separators=(",", ":")).encode("utf-8")
    body = _U32.pack(len(jb)) + jb
    return _HDR.pack(1 + 8 + len(body), T_FLEET_MAP_R, req_id) + body


def parse_fleet_map_r(body: bytes) -> dict:
    import json

    (n,) = _U32.unpack_from(body)
    return json.loads(body[_U32.size:_U32.size + n].decode("utf-8"))


def encode_dcn_fleet(req_id: int, payload: dict, secret=None, *,
                     sender=None, seq=None) -> bytes:
    """Fleet announce/heartbeat frame: T_DCN_PUSH kind=DCN_KIND_FLEET
    with a JSON body, wrapped in the RLA2 envelope when a secret is
    held (same auth + replay contract as slab pushes, ADR-007)."""
    import json

    jb = json.dumps(payload, separators=(",", ":")).encode("utf-8")
    body = _DCN_HEAD.pack(DCN_KIND_FLEET) + _U32.pack(len(jb)) + jb
    frame = _HDR.pack(1 + 8 + len(body), T_DCN_PUSH, req_id) + body
    return (wrap_dcn_auth(frame, secret, sender=sender, seq=seq)
            if secret is not None else frame)


def parse_dcn_fleet(payload: bytes) -> dict:
    """JSON announce payload from an (auth-stripped) DCN_KIND_FLEET body
    (the bytes AFTER the kind byte)."""
    import json

    if len(payload) < 4:
        raise ProtocolError("short fleet announce body")
    (n,) = _U32.unpack_from(payload)
    if len(payload) != 4 + n:
        raise ProtocolError("bad fleet announce body")
    return json.loads(payload[4:4 + n].decode("utf-8"))


def encode_dcn_lease(req_id: int, payload: dict, secret=None, *,
                     sender=None, seq=None) -> bytes:
    """Member→member lease revocation gossip (ADR-022): T_DCN_PUSH
    kind=DCN_KIND_LEASE with a JSON body ({"scope": "key"|"all",
    "key_hash": 16-hex token, "reason": str, "epoch": int}), wrapped in
    the RLA2 envelope when a secret is held — same auth + replay
    contract as fleet announces."""
    import json

    jb = json.dumps(payload, separators=(",", ":")).encode("utf-8")
    body = _DCN_HEAD.pack(DCN_KIND_LEASE) + _U32.pack(len(jb)) + jb
    frame = _HDR.pack(1 + 8 + len(body), T_DCN_PUSH, req_id) + body
    return (wrap_dcn_auth(frame, secret, sender=sender, seq=seq)
            if secret is not None else frame)


def parse_dcn_lease(payload: bytes) -> dict:
    """JSON revocation payload from an (auth-stripped) DCN_KIND_LEASE
    body (the bytes AFTER the kind byte)."""
    import json

    if len(payload) < 4:
        raise ProtocolError("short lease revocation body")
    (n,) = _U32.unpack_from(payload)
    if len(payload) != 4 + n:
        raise ProtocolError("bad lease revocation body")
    return json.loads(payload[4:4 + n].decode("utf-8"))


_HDR = struct.Struct("<IBQ")          # length, type, request_id
_ALLOW_BODY = struct.Struct("<IH")    # n, key_len
_KEYLEN = struct.Struct("<H")
_RESULT_BODY = struct.Struct("<Bqqdd")
_HEALTH_BODY = struct.Struct("<BdQ")
_ERROR_HEAD = struct.Struct("<HH")
_U32 = struct.Struct("<I")


def encode_allow_n(req_id: int, key: str, n: int) -> bytes:
    kb = key.encode("utf-8")
    body = _ALLOW_BODY.pack(n, len(kb)) + kb
    return _HDR.pack(1 + 8 + len(body), T_ALLOW_N, req_id) + body


def encode_reset(req_id: int, key: str) -> bytes:
    kb = key.encode("utf-8")
    body = _KEYLEN.pack(len(kb)) + kb
    return _HDR.pack(1 + 8 + len(body), T_RESET, req_id) + body


def encode_simple(type_: int, req_id: int) -> bytes:
    return _HDR.pack(1 + 8, type_, req_id)


def encode_result(req_id: int, res: Result) -> bytes:
    flags = (1 if res.allowed else 0) | (2 if res.fail_open else 0)
    body = _RESULT_BODY.pack(flags, res.limit, res.remaining,
                             res.retry_after, res.reset_at)
    return _HDR.pack(1 + 8 + len(body), T_RESULT, req_id) + body


def encode_ok(req_id: int) -> bytes:
    return _HDR.pack(1 + 8, T_OK, req_id)


def encode_health(req_id: int, serving: bool, uptime_s: float,
                  decisions: int) -> bytes:
    body = _HEALTH_BODY.pack(1 if serving else 0, uptime_s, decisions)
    return _HDR.pack(1 + 8 + len(body), T_HEALTH_R, req_id) + body


def encode_metrics(req_id: int, text: str) -> bytes:
    tb = text.encode("utf-8")
    body = _U32.pack(len(tb)) + tb
    return _HDR.pack(1 + 8 + len(body), T_METRICS_R, req_id) + body


def encode_error(req_id: int, code: int, msg: str) -> bytes:
    mb = msg.encode("utf-8")[:65535]
    body = _ERROR_HEAD.pack(code, len(mb)) + mb
    return _HDR.pack(1 + 8 + len(body), T_ERROR, req_id) + body


# ------------------------------------------- shm lane hello (ADR-025)

_SHM_HELLO_BODY = struct.Struct("<III")   # version, req_ring, rep_ring
_SHM_HELLO_R_HEAD = struct.Struct("<BII")  # ok, req_cap, rep_cap
_U16 = struct.Struct("<H")


def encode_shm_hello(req_id: int, req_ring_bytes: int = 0,
                     rep_ring_bytes: int = 0) -> bytes:
    """Request the shared-memory lane upgrade (0 = server default ring
    size; the server clamps to a power of two in its configured range).
    Sent on the normal socket AFTER auth, like any other request."""
    body = _SHM_HELLO_BODY.pack(1, req_ring_bytes, rep_ring_bytes)
    return _HDR.pack(1 + 8 + len(body), T_SHM_HELLO, req_id) + body


def parse_shm_hello(body: bytes):
    """-> (version, req_ring_bytes, rep_ring_bytes)."""
    if len(body) != _SHM_HELLO_BODY.size:
        raise ProtocolError("bad SHM_HELLO body")
    return _SHM_HELLO_BODY.unpack_from(body)


def encode_shm_hello_r(req_id: int, req_cap: int, rep_cap: int,
                       shm_path: str, ctrl_path: str) -> bytes:
    sp = shm_path.encode("utf-8")
    cp = ctrl_path.encode("utf-8")
    body = (_SHM_HELLO_R_HEAD.pack(1, req_cap, rep_cap)
            + _U16.pack(len(sp)) + sp + _U16.pack(len(cp)) + cp)
    return _HDR.pack(1 + 8 + len(body), T_SHM_HELLO_R, req_id) + body


def parse_shm_hello_r(body: bytes):
    """-> (req_cap, rep_cap, shm_path, ctrl_path)."""
    if len(body) < _SHM_HELLO_R_HEAD.size + 4:
        raise ProtocolError("short SHM_HELLO_R body")
    ok, req_cap, rep_cap = _SHM_HELLO_R_HEAD.unpack_from(body)
    if not ok:
        raise ProtocolError("server rejected SHM_HELLO")
    off = _SHM_HELLO_R_HEAD.size
    (sp_len,) = _U16.unpack_from(body, off)
    off += 2
    shm_path = body[off:off + sp_len].decode("utf-8")
    off += sp_len
    (cp_len,) = _U16.unpack_from(body, off)
    off += 2
    ctrl_path = body[off:off + cp_len].decode("utf-8")
    if off + cp_len != len(body):
        raise ProtocolError("bad SHM_HELLO_R body")
    return req_cap, rep_cap, shm_path, ctrl_path


# ----------------------------------------------------- policy overrides

_POLICY_SET_HEAD = struct.Struct("<BqdH")  # flags, limit, window_scale, key_len
_POLICY_R_BODY = struct.Struct("<Bqd")     # found, limit, window_scale


def encode_policy_set(req_id: int, key: str, limit=None,
                      window_scale: float = 1.0) -> bytes:
    kb = key.encode("utf-8")
    flags = 1 if limit is not None else 0
    body = _POLICY_SET_HEAD.pack(flags, limit if limit is not None else 0,
                                 float(window_scale), len(kb)) + kb
    return _HDR.pack(1 + 8 + len(body), T_POLICY_SET, req_id) + body


def parse_policy_set(body: bytes):
    """-> (key, limit | None, window_scale)."""
    flags, limit, scale, key_len = _POLICY_SET_HEAD.unpack_from(body)
    if key_len > MAX_KEY_LEN or len(body) != _POLICY_SET_HEAD.size + key_len:
        raise ProtocolError("bad POLICY_SET body")
    key = body[_POLICY_SET_HEAD.size:].decode("utf-8")
    return key, (limit if flags & 1 else None), scale


def encode_policy_key(type_: int, req_id: int, key: str) -> bytes:
    """POLICY_GET / POLICY_DEL share the RESET body shape."""
    kb = key.encode("utf-8")
    body = _KEYLEN.pack(len(kb)) + kb
    return _HDR.pack(1 + 8 + len(body), type_, req_id) + body


def encode_policy_r(req_id: int, found: bool, limit: int,
                    window_scale: float) -> bytes:
    body = _POLICY_R_BODY.pack(1 if found else 0, limit, float(window_scale))
    return _HDR.pack(1 + 8 + len(body), T_POLICY_R, req_id) + body


def parse_policy_r(body: bytes):
    """-> (found, limit, window_scale)."""
    found, limit, scale = _POLICY_R_BODY.unpack(body)
    return bool(found), limit, scale


# ------------------------------------------------- durability snapshots

_SNAPSHOT_R_BODY = struct.Struct("<QQd")  # snapshot_id, wal_seq, duration_s


def encode_snapshot_r(req_id: int, snapshot_id: int, wal_seq: int,
                      duration_s: float) -> bytes:
    body = _SNAPSHOT_R_BODY.pack(snapshot_id, wal_seq, float(duration_s))
    return _HDR.pack(1 + 8 + len(body), T_SNAPSHOT_R, req_id) + body


def parse_snapshot_r(body: bytes) -> Tuple[int, int, float]:
    """-> (snapshot_id, wal_seq, duration_s)."""
    snapshot_id, wal_seq, duration = _SNAPSHOT_R_BODY.unpack(body)
    return snapshot_id, wal_seq, duration


# ------------------------------------------- quota leases (ADR-022)
#
# GRANT debits the requested budget from the key's live window UPFRONT
# (through the server's normal decide path), so the global bound holds
# no matter what the client does with the tokens afterwards. RENEW
# reports local consumption (for the audit mirror) and asks for a
# top-up; RETURN reports the final count and releases the grant —
# WITHOUT re-crediting unused budget (the window already charged it;
# failing toward false-denies is the documented side).

_LEASE_GRANT_HEAD = struct.Struct("<QIdH")   # client, want, ttl_want, key_len
_LEASE_RENEW_HEAD = struct.Struct("<QQQIH")  # client, lease, consumed, want, key_len
_LEASE_RETURN_HEAD = struct.Struct("<QQQH")  # client, lease, consumed, key_len
_LEASE_R_BODY = struct.Struct("<BQqdqQ")     # flags, lease, budget, ttl, limit, epoch
_LEASE_REVOKE_HEAD = struct.Struct("<BQI")   # reason, epoch, count (then count u64)

#: Revocation reasons (wire u8 + journal/metrics label).
LEASE_REV_POLICY = 1      # per-key override set/deleted
LEASE_REV_LIMIT = 2       # update_limit / update_window
LEASE_REV_CONTROLLER = 3  # AIMD tighten on the key's scope (ADR-020)
LEASE_REV_EPOCH = 4       # fleet ownership moved (ADR-017/PR 11 handoff)
LEASE_REV_SHUTDOWN = 5    # graceful server shutdown
LEASE_REV_MANUAL = 6      # operator drill
LEASE_REASONS = {LEASE_REV_POLICY: "policy", LEASE_REV_LIMIT: "limit",
                 LEASE_REV_CONTROLLER: "controller",
                 LEASE_REV_EPOCH: "epoch", LEASE_REV_SHUTDOWN: "shutdown",
                 LEASE_REV_MANUAL: "manual"}


def encode_lease_grant(req_id: int, client_id: int, key: str, want: int,
                       ttl_want: float = 0.0) -> bytes:
    kb = key.encode("utf-8")
    body = _LEASE_GRANT_HEAD.pack(client_id, want, float(ttl_want),
                                  len(kb)) + kb
    return _HDR.pack(1 + 8 + len(body), T_LEASE_GRANT, req_id) + body


def parse_lease_grant(body: bytes):
    """-> (client_id, key, want, ttl_want)."""
    client, want, ttl_want, key_len = _LEASE_GRANT_HEAD.unpack_from(body)
    if key_len > MAX_KEY_LEN or len(body) != _LEASE_GRANT_HEAD.size + key_len:
        raise ProtocolError("bad LEASE_GRANT body")
    return client, body[_LEASE_GRANT_HEAD.size:].decode("utf-8"), want, ttl_want


def encode_lease_renew(req_id: int, client_id: int, lease_id: int, key: str,
                       consumed: int, want: int) -> bytes:
    kb = key.encode("utf-8")
    body = _LEASE_RENEW_HEAD.pack(client_id, lease_id, consumed, want,
                                  len(kb)) + kb
    return _HDR.pack(1 + 8 + len(body), T_LEASE_RENEW, req_id) + body


def parse_lease_renew(body: bytes):
    """-> (client_id, lease_id, key, consumed, want)."""
    client, lease, consumed, want, key_len = _LEASE_RENEW_HEAD.unpack_from(body)
    if key_len > MAX_KEY_LEN or len(body) != _LEASE_RENEW_HEAD.size + key_len:
        raise ProtocolError("bad LEASE_RENEW body")
    return (client, lease, body[_LEASE_RENEW_HEAD.size:].decode("utf-8"),
            consumed, want)


def encode_lease_return(req_id: int, client_id: int, lease_id: int, key: str,
                        consumed: int) -> bytes:
    kb = key.encode("utf-8")
    body = _LEASE_RETURN_HEAD.pack(client_id, lease_id, consumed, len(kb)) + kb
    return _HDR.pack(1 + 8 + len(body), T_LEASE_RETURN, req_id) + body


def parse_lease_return(body: bytes):
    """-> (client_id, lease_id, key, consumed)."""
    client, lease, consumed, key_len = _LEASE_RETURN_HEAD.unpack_from(body)
    if key_len > MAX_KEY_LEN or len(body) != _LEASE_RETURN_HEAD.size + key_len:
        raise ProtocolError("bad LEASE_RETURN body")
    return (client, lease, body[_LEASE_RETURN_HEAD.size:].decode("utf-8"),
            consumed)


def encode_lease_r(req_id: int, granted: bool, lease_id: int, budget: int,
                   ttl_s: float, limit: int, epoch: int = 0) -> bytes:
    """``budget`` is the number of tokens ADDED by this answer (initial
    grant or renew top-up) — the client adds it to its local counter.
    ``granted`` False means lease refused / released; the client serves
    the key from the wire path."""
    body = _LEASE_R_BODY.pack(1 if granted else 0, lease_id, budget,
                              float(ttl_s), limit, epoch)
    return _HDR.pack(1 + 8 + len(body), T_LEASE_R, req_id) + body


def parse_lease_r(body: bytes):
    """-> (granted, lease_id, budget, ttl_s, limit, epoch)."""
    flags, lease, budget, ttl_s, limit, epoch = _LEASE_R_BODY.unpack(body)
    return bool(flags & 1), lease, budget, ttl_s, limit, epoch


def encode_lease_revoke(reason: int, epoch: int, lease_ids) -> bytes:
    """Unsolicited push (req_id=0). An EMPTY id list revokes every lease
    the receiving client holds from this server (the revoke-all form —
    update_limit, shutdown, epoch bumps)."""
    ids = list(lease_ids)
    body = _LEASE_REVOKE_HEAD.pack(reason, epoch, len(ids))
    body += b"".join(_TRACE_ID.pack(i) for i in ids)
    return _HDR.pack(1 + 8 + len(body), T_LEASE_REVOKE, 0) + body


def parse_lease_revoke(body: bytes):
    """-> (reason, epoch, [lease_id, ...])."""
    reason, epoch, count = _LEASE_REVOKE_HEAD.unpack_from(body)
    need = _LEASE_REVOKE_HEAD.size + 8 * count
    if len(body) != need:
        raise ProtocolError("bad LEASE_REVOKE body")
    ids = [_TRACE_ID.unpack_from(body, _LEASE_REVOKE_HEAD.size + 8 * i)[0]
           for i in range(count)]
    return reason, epoch, ids


_BATCH_ITEM = struct.Struct("<IH")       # n, key_len (per request)
_BATCH_RES_HEAD = struct.Struct("<qI")   # limit, count
_BATCH_RES_ITEM = struct.Struct("<Bqdd")  # flags, remaining, retry, reset


def encode_allow_batch(req_id: int, keys, ns) -> bytes:
    parts = [_U32.pack(len(keys))]
    for key, n in zip(keys, ns):
        kb = key.encode("utf-8")
        parts.append(_BATCH_ITEM.pack(n, len(kb)))
        parts.append(kb)
    body = b"".join(parts)
    return _HDR.pack(1 + 8 + len(body), T_ALLOW_BATCH, req_id) + body


def parse_allow_batch(body: bytes):
    """-> (keys, ns). Bounded by MAX_FRAME at the header layer."""
    (count,) = _U32.unpack_from(body)
    off = _U32.size
    keys, ns = [], []
    for _ in range(count):
        if off + _BATCH_ITEM.size > len(body):
            raise ProtocolError("truncated ALLOW_BATCH body")
        n, key_len = _BATCH_ITEM.unpack_from(body, off)
        off += _BATCH_ITEM.size
        if key_len > MAX_KEY_LEN or off + key_len > len(body):
            raise ProtocolError("bad ALLOW_BATCH key")
        keys.append(body[off:off + key_len].decode("utf-8"))
        ns.append(n)
        off += key_len
    if off != len(body):
        raise ProtocolError("trailing bytes in ALLOW_BATCH body")
    return keys, ns


def encode_result_batch_views(req_id: int, limit: int, results) -> list:
    """T_RESULT_BATCH frame as a writev-style buffer list (ISSUE-20
    satellite, mirror of encode_result_hashed_views): frame header +
    batch head as one small bytes object, then each 25-byte result
    record as its own buffer. The SINGLE source of the batch framing —
    encode_result_batch joins these parts for the one-buffer form, so
    the scatter-gather path is byte-identical by construction. The
    asyncio server hands the list to transport.writelines (a true
    writev under uvloop); the encoder never joins the full body."""
    n = len(results)
    body_len = _BATCH_RES_HEAD.size + n * _BATCH_RES_ITEM.size
    parts = [_HDR.pack(1 + 8 + body_len, T_RESULT_BATCH, req_id)
             + _BATCH_RES_HEAD.pack(limit, n)]
    for r in results:
        flags = (1 if r.allowed else 0) | (2 if r.fail_open else 0)
        parts.append(_BATCH_RES_ITEM.pack(flags, r.remaining, r.retry_after,
                                          r.reset_at))
    return parts


def encode_result_batch(req_id: int, limit: int, results) -> bytes:
    return b"".join(encode_result_batch_views(req_id, limit, results))


def parse_result_batch(body: bytes):
    limit, count = _BATCH_RES_HEAD.unpack_from(body)
    off = _BATCH_RES_HEAD.size
    out = []
    for _ in range(count):
        flags, remaining, retry, reset = _BATCH_RES_ITEM.unpack_from(body, off)
        off += _BATCH_RES_ITEM.size
        out.append(Result(allowed=bool(flags & 1), limit=limit,
                          remaining=remaining, retry_after=retry,
                          reset_at=reset, fail_open=bool(flags & 2)))
    return out


#: Structured view of one RESULT_BATCH row (exactly _BATCH_RES_ITEM's
#: packed little-endian layout — 25 bytes, no padding).
_BATCH_RES_REC = None


def parse_result_batch_columnar(body: bytes):
    """RESULT_BATCH as a columnar BatchResult (ADR-019): one structured
    ``np.frombuffer`` over the packed per-row records instead of
    ``count`` struct unpacks + Result objects — the fleet forwarder's
    string-fallback legs merge through scatter_merge's numpy path."""
    import numpy as np

    from ratelimiter_tpu.core.types import BatchResult

    global _BATCH_RES_REC
    if _BATCH_RES_REC is None:
        _BATCH_RES_REC = np.dtype([("flags", "u1"), ("remaining", "<i8"),
                                   ("retry", "<f8"), ("reset", "<f8")])
        assert _BATCH_RES_REC.itemsize == _BATCH_RES_ITEM.size
    limit, count = _BATCH_RES_HEAD.unpack_from(body)
    if len(body) != _BATCH_RES_HEAD.size + count * _BATCH_RES_ITEM.size:
        raise ProtocolError(
            f"bad RESULT_BATCH body ({len(body)}B for count={count})")
    rec = np.frombuffer(body, dtype=_BATCH_RES_REC, count=count,
                        offset=_BATCH_RES_HEAD.size)
    flags = rec["flags"]
    return BatchResult(allowed=(flags & 1).astype(bool), limit=limit,
                       remaining=rec["remaining"],
                       retry_after=rec["retry"], reset_at=rec["reset"],
                       fail_open=bool((flags & 2).any()))


# ---------------------------------------------- hashed bulk lane (ADR-011)

_HASHED_HEAD = _U32                        # count
_HASHED_RES_HEAD = struct.Struct("<BqI")   # batch_flags, limit, count


def encode_allow_hashed(req_id: int, ids, ns=None) -> bytes:
    """Columnar raw-u64-id frame: the bulk lane's request encode is two
    array ``tobytes`` calls — no per-request packing."""
    import numpy as np

    ids = np.ascontiguousarray(ids, dtype="<u8")
    if ns is None:
        ns_arr = np.ones(ids.shape[0], dtype="<u4")
    else:
        ns_arr = np.ascontiguousarray(ns, dtype="<u4")
    if ns_arr.shape[0] != ids.shape[0]:
        raise ValueError("ids and ns must have equal length")
    body = (_HASHED_HEAD.pack(ids.shape[0]) + ids.tobytes()
            + ns_arr.tobytes())
    return _HDR.pack(1 + 8 + len(body), T_ALLOW_HASHED, req_id) + body


def parse_allow_hashed(body: bytes):
    """-> (ids uint64, ns uint32): zero-copy np.frombuffer VIEWS into the
    frame body — no per-request Python objects anywhere on this path
    (the columnar layout exists exactly so this is possible)."""
    import numpy as np

    if len(body) < 4:
        raise ProtocolError("short ALLOW_HASHED body")
    (count,) = _HASHED_HEAD.unpack_from(body)
    if len(body) != 4 + 12 * count:
        raise ProtocolError(
            f"bad ALLOW_HASHED body ({len(body)}B for count={count})")
    ids = np.frombuffer(body, dtype="<u8", count=count, offset=4)
    ns = np.frombuffer(body, dtype="<u4", count=count,
                       offset=4 + 8 * count)
    return ids, ns


def encode_result_hashed(req_id: int, res) -> bytes:
    """Columnar response from a BatchResult, as ONE bytes frame. Wire-lane
    results arrive packed (BatchResult.wire_packed,
    core/types.wire_pack) and frame via the shared view builder below
    (one join, no per-column re-packing); results without packed buffers
    (fail-open, pre-resolved, client-constructed) take the np.packbits
    path."""
    import numpy as np

    wp = getattr(res, "wire_packed", None)
    if wp is not None:
        return b"".join(bytes(v)
                        for v in encode_result_hashed_views(req_id, res))
    b = len(res)
    flags = 2 if res.fail_open else 0
    bits = np.packbits(np.asarray(res.allowed, dtype=bool),
                       bitorder="little")
    body = (_HASHED_RES_HEAD.pack(flags, res.limit, b)
            + bits.tobytes()
            + np.ascontiguousarray(res.remaining, dtype="<i8").tobytes()
            + np.ascontiguousarray(res.retry_after, dtype="<f8").tobytes()
            + np.ascontiguousarray(res.reset_at, dtype="<f8").tobytes())
    return _HDR.pack(1 + 8 + len(body), T_RESULT_HASHED, req_id) + body


def encode_result_hashed_views(req_id: int, res) -> list:
    """T_RESULT_HASHED frame as a writev-style buffer list (ADR-011
    residual, ISSUE-5 satellite): header + allow-mask bytes in one small
    bytes object, then the three value columns as zero-copy MEMORYVIEWS
    straight over the resolve's ``wire_packed`` words buffer. This
    is the SINGLE source of the packed framing (pad-bit masking, column
    offsets); encode_result_hashed joins these views for the one-buffer
    form. The ENCODER makes zero copies of the columns; downstream, the
    asyncio server hands the list to transport.writelines — a true
    scatter-gather under uvloop, while stock asyncio transports still
    concatenate once at the socket layer (the former per-column
    ``tobytes`` copies and the encoder-level join are gone either way).
    Results without packed buffers fall back to the single-buffer
    encode.

    tests/test_hashed_wire.py asserts the zero-copy property by buffer
    identity: each returned column view shares memory with the resolve
    fetch, byte for byte."""
    wp = getattr(res, "wire_packed", None)
    if wp is None:
        return [encode_result_hashed(req_id, res)]
    b = len(res)
    flags = 2 if res.fail_open else 0
    bits_arr, words, padded = wp[0], wp[1], wp[2]
    # Row-window form (BatchResult.rows, ADR-013): frame the sub-range
    # [off, off+b) of a coalesced window's packed buffers — the value
    # columns stay offset memoryviews either way; the mask is a byte
    # slice when the frame landed byte-aligned in the window (the common
    # case: frame sizes are multiples of 8) and a packbits re-pack of
    # just this frame's bits otherwise.
    off = wp[3] if len(wp) > 3 else 0
    nb = (b + 7) // 8
    if off & 7 == 0:
        lo = off >> 3
        bits = bytearray(bits_arr[lo:lo + nb].tobytes())
        if b & 7 and nb:
            # Zero the trailing bits in the final partial byte (pad rows
            # or the next frame's rows) so frame bytes are deterministic.
            bits[-1] &= (1 << (b & 7)) - 1
    else:
        import numpy as np

        # Unpack only this frame's byte range (O(frame), not O(window)
        # — a window of odd-sized frames would otherwise unpack the
        # whole 2*max_batch-bit mask once per frame).
        lo = off >> 3
        chunk = np.asarray(bits_arr[lo:(off + b + 7) >> 3])
        rows_bits = np.unpackbits(chunk, bitorder="little")[
            off - 8 * lo:off - 8 * lo + b]
        bits = bytearray(np.packbits(rows_bits, bitorder="little").tobytes())
    body_len = _HASHED_RES_HEAD.size + nb + 24 * b
    head = (_HDR.pack(1 + 8 + body_len, T_RESULT_HASHED, req_id)
            + _HASHED_RES_HEAD.pack(flags, res.limit, b) + bytes(bits))
    return [head,
            memoryview(words[off:off + b]).cast("B"),
            memoryview(words[padded + off:padded + off + b]).cast("B"),
            memoryview(words[2 * padded + off:2 * padded + off + b])
            .cast("B")]


def parse_result_hashed(body: bytes):
    """-> BatchResult with frombuffer-view columns (client side)."""
    import numpy as np

    from ratelimiter_tpu.core.types import BatchResult

    if len(body) < _HASHED_RES_HEAD.size:
        raise ProtocolError("short RESULT_HASHED body")
    flags, limit, count = _HASHED_RES_HEAD.unpack_from(body)
    nb = (count + 7) // 8
    off = _HASHED_RES_HEAD.size
    if len(body) != off + nb + 24 * count:
        raise ProtocolError(
            f"bad RESULT_HASHED body ({len(body)}B for count={count})")
    bits = np.frombuffer(body, dtype=np.uint8, count=nb, offset=off)
    allowed = np.unpackbits(bits, bitorder="little")[:count].astype(bool)
    off += nb
    remaining = np.frombuffer(body, dtype="<i8", count=count, offset=off)
    off += 8 * count
    retry = np.frombuffer(body, dtype="<f8", count=count, offset=off)
    off += 8 * count
    reset = np.frombuffer(body, dtype="<f8", count=count, offset=off)
    return BatchResult(allowed=allowed, limit=limit, remaining=remaining,
                       retry_after=retry, reset_at=reset,
                       fail_open=bool(flags & 2))


@dataclass
class Frame:
    type: int
    req_id: int
    body: bytes


class ProtocolError(RateLimiterError):
    """Malformed frame — the connection is beyond recovery."""


def parse_header(buf: bytes, *, allow_dcn: bool = False) -> Tuple[int, int, int]:
    """(payload_length, type, req_id) from the 13 header bytes.

    ``allow_dcn`` raises the size cap for T_DCN_PUSH frames — ONLY a
    server that actually participates in DCN should pass it, otherwise
    any client could force MAX_DCN_FRAME-sized buffering per connection
    just by labeling frames (memory DoS on plain deployments)."""
    length, type_, req_id = _HDR.unpack_from(buf)
    # The size cap keys on the BASE type: a traced and/or deadline-
    # stamped DCN push (TRACE_FLAG/DEADLINE_FLAG) still deserves the
    # slab-sized cap on a DCN-enabled server.
    base = type_ & ~(_REQ_FLAGS | FORWARD_FLAG) if type_ < 128 else type_
    cap = MAX_DCN_FRAME if (allow_dcn and base == T_DCN_PUSH) else MAX_FRAME
    if length < 9 or length > cap:
        raise ProtocolError(f"bad frame length {length}")
    return length, type_, req_id


HEADER_SIZE = _HDR.size  # 13


def parse_allow_n(body: bytes) -> Tuple[str, int]:
    n, key_len = _ALLOW_BODY.unpack_from(body)
    if key_len > MAX_KEY_LEN or len(body) != _ALLOW_BODY.size + key_len:
        raise ProtocolError("bad ALLOW_N body")
    return body[_ALLOW_BODY.size:].decode("utf-8"), n


def parse_reset(body: bytes) -> str:
    (key_len,) = _KEYLEN.unpack_from(body)
    if key_len > MAX_KEY_LEN or len(body) != _KEYLEN.size + key_len:
        raise ProtocolError("bad RESET body")
    return body[_KEYLEN.size:].decode("utf-8")


def parse_result(body: bytes) -> Result:
    flags, limit, remaining, retry_after, reset_at = _RESULT_BODY.unpack(body)
    return Result(allowed=bool(flags & 1), limit=limit, remaining=remaining,
                  retry_after=retry_after, reset_at=reset_at,
                  fail_open=bool(flags & 2))


def parse_health(body: bytes) -> Tuple[bool, float, int]:
    status, uptime, decisions = _HEALTH_BODY.unpack(body)
    return bool(status), uptime, decisions


def parse_metrics(body: bytes) -> str:
    (n,) = _U32.unpack_from(body)
    return body[_U32.size:_U32.size + n].decode("utf-8")


def parse_error(body: bytes) -> Tuple[int, str]:
    code, msg_len = _ERROR_HEAD.unpack_from(body)
    return code, body[_ERROR_HEAD.size:_ERROR_HEAD.size + msg_len].decode("utf-8")


# ----------------------------------------------------------- DCN frames
#
# T_DCN_PUSH body:
#   u8 kind
#   kind=DCN_KIND_SLABS: s64 sub_us | u32 count | s64 periods[count] |
#                        count * d*w int32 slabs (C order)
#   kind=DCN_KIND_DEBT:  d*w int64 delta (C order)
# The receiver validates payload size against ITS OWN (d, w) geometry
# and, for slabs, the sub-window duration (periods are denominated in
# sub_us units — a window change renumbers them, so a pod mid-window-
# migration must not merge old-unit periods). Mismatches answer
# E_INVALID_CONFIG, never a reshaped/renumbered merge.

_DCN_HEAD = struct.Struct("<B")
_S64 = struct.Struct("<q")


#: Auth envelope for T_DCN_PUSH bodies. A push injects counter mass into
#: the receiver's limiter, so an open serving port accepting pushes is a
#: targeted false-deny lever for anyone with network reach; deployments
#: that cannot firewall the port share a secret instead. Two envelope
#: versions:
#:
#:   RLA1 (legacy): MAGIC + HMAC-SHA256(secret, body) + body — no replay
#:        protection (a captured push re-sends forever).
#:   RLA2:          MAGIC2 + HMAC-SHA256(secret, sender||seq||body)
#:                  + u64 sender + u64 seq + body — the sender id and a
#:        monotonic per-sender sequence are INSIDE the HMAC, so receivers
#:        reject stale/duplicate values (DcnReplayGuard; ADR-007).
#:
#: A kind byte is 1 or 2, so the 'R' magic is unambiguous. A server
#: WITHOUT a secret accepts all forms (open by configuration); a server
#: WITH one accepts only valid RLA2 — untagged, mistagged, and LEGACY
#: RLA1 pushes are rejected (RLA1's replayability is the hole RLA2
#: closes). See docs/OPERATIONS.md "Trust boundaries".
DCN_AUTH_MAGIC = b"RLA1"
DCN_AUTH_MAGIC2 = b"RLA2"
_DCN_TAG_LEN = 32
_DCN_SEQ = struct.Struct("<QQ")   # sender id, sequence


class DcnReplayGuard:
    """Per-sender monotonic-sequence filter for T_DCN_PUSH (RLA2).

    Sequences are wall-clock-seeded microseconds (DcnPusher), so a
    sender's seq is also a coarse timestamp: a FIRST-CONTACT frame whose
    seq is older than ``max_age_s`` is rejected too, bounding replay of a
    dead sender incarnation's captured stream to that window (the
    documented residual — receivers keep no cross-restart state; ADR-007
    §replay). Thread-safe; only meaningful as a security control when
    the frames are HMAC-verified (with no secret anyone can mint fresh
    sender ids), but it still deduplicates accidental re-delivery there.
    """

    #: Sender-table bound: evicting the lowest-seq (oldest) sender keeps
    #: an open receiver's memory O(1) under sender-id spray.
    MAX_SENDERS = 4096

    def __init__(self, max_age_s: float = 300.0, time_fn=None):
        import threading
        import time as _time

        self.max_age_s = float(max_age_s)
        self._time = time_fn if time_fn is not None else _time.time
        self._last: dict = {}
        self._lock = threading.Lock()
        self.rejected = 0

    def check(self, sender: int, seq: int) -> None:
        """Record (sender, seq); raises InvalidConfigError (a typed wire
        error) on a stale or duplicate sequence."""
        from ratelimiter_tpu.core.errors import InvalidConfigError

        with self._lock:
            last = self._last.get(sender)
            if last is None:
                floor = int((self._time() - self.max_age_s) * 1e6)
                if seq < floor:
                    self.rejected += 1
                    raise InvalidConfigError(
                        f"stale DCN push rejected (sender seq {seq} is "
                        f"older than the {self.max_age_s:g}s replay window)")
            elif seq <= last:
                self.rejected += 1
                raise InvalidConfigError(
                    f"replayed DCN push rejected (seq {seq} <= last "
                    f"accepted {last} for this sender)")
            self._last[sender] = seq
            if len(self._last) > self.MAX_SENDERS:
                self._last.pop(min(self._last, key=self._last.get))


def wrap_dcn_auth(frame: bytes, secret: str, *, sender=None,
                  seq=None) -> bytes:
    """Re-frame a T_DCN_PUSH frame with the HMAC envelope on its body:
    RLA2 (sequenced — what DcnPusher sends) when ``sender``/``seq`` are
    given, legacy RLA1 otherwise."""
    import hashlib
    import hmac as _hmac

    length, type_, req_id = _HDR.unpack_from(frame)
    body = frame[HEADER_SIZE:]
    if sender is not None:
        sb = _DCN_SEQ.pack(sender, seq)
        tag = _hmac.new(secret.encode(), sb + body, hashlib.sha256).digest()
        body = DCN_AUTH_MAGIC2 + tag + sb + body
    else:
        tag = _hmac.new(secret.encode(), body, hashlib.sha256).digest()
        body = DCN_AUTH_MAGIC + tag + body
    return _HDR.pack(1 + 8 + len(body), type_, req_id) + body


def unwrap_dcn_auth(body: bytes, secret, guard: "DcnReplayGuard | None" =
                    None) -> bytes:
    """Verify/strip the auth envelope per the receiver's configuration.
    Raises InvalidConfigError (a typed wire error) on missing/bad tags
    when a secret is required and on stale/duplicate sequences when a
    replay guard is installed."""
    from ratelimiter_tpu.core.errors import InvalidConfigError

    if body[:4] == DCN_AUTH_MAGIC2:
        head = 4 + _DCN_TAG_LEN + _DCN_SEQ.size
        if len(body) < head:
            raise ProtocolError("truncated DCN auth envelope")
        tag = body[4:4 + _DCN_TAG_LEN]
        signed = body[4 + _DCN_TAG_LEN:]
        sender, seq = _DCN_SEQ.unpack_from(signed)
        if secret is not None:
            import hashlib
            import hmac as _hmac

            want = _hmac.new(secret.encode(), signed, hashlib.sha256).digest()
            if not _hmac.compare_digest(tag, want):
                raise InvalidConfigError("DCN push auth tag mismatch")
        # Sequence check AFTER authentication: a forged frame must not be
        # able to advance (or poison) a genuine sender's watermark.
        if guard is not None:
            guard.check(sender, seq)
        return body[head:]
    if body[:4] == DCN_AUTH_MAGIC:
        if len(body) < 4 + _DCN_TAG_LEN:
            raise ProtocolError("truncated DCN auth envelope")
        tag, rest = body[4:4 + _DCN_TAG_LEN], body[4 + _DCN_TAG_LEN:]
        if secret is not None:
            # Legacy RLA1 carries no sequence, so a captured frame
            # replays forever — a secret-requiring receiver rejects it
            # outright (senders on this codebase always send RLA2 when
            # they hold a secret).
            raise InvalidConfigError(
                "legacy unsequenced DCN envelope (RLA1) rejected: this "
                "server requires replay-protected pushes (RLA2)")
        return rest
    if secret is not None:
        raise InvalidConfigError(
            "unauthenticated DCN push rejected (this server requires "
            "--dcn-secret)")
    return body


def encode_dcn_slabs(req_id: int, periods, slabs, sub_us: int,
                     secret=None, *, sender=None, seq=None) -> bytes:
    """periods int64[k] in sub_us units, slabs int32[k, d, w]
    (export_completed output)."""
    import numpy as np

    k = int(periods.shape[0])
    body = (_DCN_HEAD.pack(DCN_KIND_SLABS) + _S64.pack(sub_us)
            + _U32.pack(k)
            + np.ascontiguousarray(periods, dtype=np.int64).tobytes()
            + np.ascontiguousarray(slabs, dtype=np.int32).tobytes())
    frame = _HDR.pack(1 + 8 + len(body), T_DCN_PUSH, req_id) + body
    return (wrap_dcn_auth(frame, secret, sender=sender, seq=seq)
            if secret is not None else frame)


def encode_dcn_debt(req_id: int, delta, secret=None, *, sender=None,
                    seq=None) -> bytes:
    """delta int64[d, w] (export_debt output)."""
    import numpy as np

    body = (_DCN_HEAD.pack(DCN_KIND_DEBT)
            + np.ascontiguousarray(delta, dtype=np.int64).tobytes())
    frame = _HDR.pack(1 + 8 + len(body), T_DCN_PUSH, req_id) + body
    return (wrap_dcn_auth(frame, secret, sender=sender, seq=seq)
            if secret is not None else frame)


def parse_dcn(body: bytes, d: int, w: int, sub_us: int):
    """-> (DCN_KIND_SLABS, periods int64[k], slabs int32[k,d,w]) or
    (DCN_KIND_DEBT, delta int64[d,w], None), validated against the
    receiver's geometry (incl. the sub-window duration for slabs)."""
    import numpy as np

    if len(body) < 1:
        raise ProtocolError("empty DCN body")
    (kind,) = _DCN_HEAD.unpack_from(body)
    payload = body[1:]
    if kind == DCN_KIND_SLABS:
        if len(payload) < 12:
            raise ProtocolError("short DCN slabs body")
        (peer_sub,) = _S64.unpack_from(payload)
        if peer_sub != sub_us:
            from ratelimiter_tpu.core.errors import InvalidConfigError

            raise InvalidConfigError(
                f"DCN peer sub-window {peer_sub}us != local {sub_us}us "
                "(window mismatch or mid-migration) — periods would "
                "merge into the wrong sub-windows")
        (k,) = _U32.unpack_from(payload, 8)
        want = 12 + k * 8 + k * d * w * 4
        if len(payload) != want:
            raise ProtocolError(
                f"DCN slabs payload {len(payload)}B != {want}B for "
                f"k={k} d={d} w={w} (geometry mismatch?)")
        periods = np.frombuffer(payload, dtype=np.int64, count=k, offset=12)
        slabs = np.frombuffer(payload, dtype=np.int32,
                              offset=12 + k * 8).reshape(k, d, w)
        return kind, periods, slabs
    if kind == DCN_KIND_DEBT:
        want = d * w * 8
        if len(payload) != want:
            raise ProtocolError(
                f"DCN debt payload {len(payload)}B != {want}B for "
                f"d={d} w={w} (geometry mismatch?)")
        return kind, np.frombuffer(payload, dtype=np.int64).reshape(d, w), None
    raise ProtocolError(f"unknown DCN kind {kind}")
