"""gRPC adapter (serving/grpc_server.py) for the checked-in proto
contract (api/proto/ratelimiter.proto) — the reference's planned L5
surface (its ``docs/ARCHITECTURE.md`` gRPC service). Skips when the
optional grpcio runtime (or protoc) is absent."""

from __future__ import annotations

import pytest

from netutil import free_port

grpc = pytest.importorskip("grpc")

from ratelimiter_tpu import (  # noqa: E402
    Algorithm,
    Config,
    ManualClock,
    create_limiter,
)
from ratelimiter_tpu.core.types import Result  # noqa: E402
from ratelimiter_tpu.serving.grpc_server import (  # noqa: E402
    GrpcRateLimitServer,
    _load_pb2,
    grpc_available,
    grpc_server_for_limiter,
)

if not grpc_available():  # pragma: no cover - env without protoc
    pytest.skip("protoc or grpcio unusable here", allow_module_level=True)

T0 = 1_700_000_000.0


@pytest.fixture()
def pb2():
    return _load_pb2()


@pytest.fixture()
def served():
    clock = ManualClock(T0)
    cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=3, window=60.0)
    lim = create_limiter(cfg, backend="exact", clock=clock)
    srv = grpc_server_for_limiter(lim)
    srv.start()
    channel = grpc.insecure_channel(f"127.0.0.1:{srv.port}")
    yield channel, lim, clock
    channel.close()
    srv.shutdown()
    lim.close()


def _stub(channel, pb2):
    """Hand-rolled method callables (no grpc_tools-generated stub)."""
    base = "/ratelimiter.v1.RateLimiter/"

    def method(name, req_cls, resp_cls):
        return channel.unary_unary(
            base + name, request_serializer=req_cls.SerializeToString,
            response_deserializer=resp_cls.FromString)

    class Stub:
        Allow = method("Allow", pb2.AllowRequest, pb2.AllowResponse)
        AllowN = method("AllowN", pb2.AllowNRequest, pb2.AllowResponse)
        AllowBatch = method("AllowBatch", pb2.AllowBatchRequest,
                            pb2.AllowBatchResponse)
        Reset = method("Reset", pb2.ResetRequest, pb2.ResetResponse)
        Health = method("Health", pb2.HealthRequest, pb2.HealthResponse)
        SetOverride = method("SetOverride", pb2.SetOverrideRequest,
                             pb2.OverrideResponse)
        GetOverride = method("GetOverride", pb2.GetOverrideRequest,
                             pb2.OverrideResponse)
        DeleteOverride = method("DeleteOverride", pb2.DeleteOverrideRequest,
                                pb2.DeleteOverrideResponse)
        SetTenant = method("SetTenant", pb2.SetTenantRequest,
                           pb2.TenantResponse)
        GetTenant = method("GetTenant", pb2.GetTenantRequest,
                           pb2.TenantResponse)
        DeleteTenant = method("DeleteTenant", pb2.DeleteTenantRequest,
                              pb2.DeleteTenantResponse)
        AssignTenant = method("AssignTenant", pb2.AssignTenantRequest,
                              pb2.AssignTenantResponse)
        UnassignTenant = method("UnassignTenant", pb2.UnassignTenantRequest,
                                pb2.UnassignTenantResponse)

    return Stub


class TestGrpcServer:
    def test_allow_deny_reset_roundtrip(self, served, pb2):
        channel, _, _ = served
        stub = _stub(channel, pb2)
        for i in range(3):
            resp = stub.Allow(pb2.AllowRequest(key="u1"))
            assert resp.allowed and resp.remaining == 2 - i
            assert resp.limit == 3
        resp = stub.Allow(pb2.AllowRequest(key="u1"))
        assert not resp.allowed and resp.retry_after > 0
        assert resp.reset_at > T0
        stub.Reset(pb2.ResetRequest(key="u1"))
        assert stub.Allow(pb2.AllowRequest(key="u1")).allowed

    def test_allow_n_all_or_nothing(self, served, pb2):
        channel, _, _ = served
        stub = _stub(channel, pb2)
        assert stub.AllowN(pb2.AllowNRequest(key="u2", n=3)).allowed
        resp = stub.AllowN(pb2.AllowNRequest(key="u2", n=2))
        assert not resp.allowed and resp.remaining == 0  # denial consumed 0

    def test_allow_batch_in_order_with_sequencing(self, served, pb2):
        channel, _, _ = served
        stub = _stub(channel, pb2)
        req = pb2.AllowBatchRequest(items=[
            pb2.AllowBatchRequest.Item(key="b1", n=2),
            pb2.AllowBatchRequest.Item(key="b2", n=1),
            pb2.AllowBatchRequest.Item(key="b1", n=1),
            pb2.AllowBatchRequest.Item(key="b1", n=1),   # 4th unit: denied
        ])
        out = stub.AllowBatch(req)
        assert [r.allowed for r in out.results] == [True, True, True, False]

    def test_health(self, served, pb2):
        channel, _, _ = served
        stub = _stub(channel, pb2)
        stub.Allow(pb2.AllowRequest(key="h"))
        h = stub.Health(pb2.HealthRequest())
        assert h.serving and h.uptime_seconds >= 0

    def test_error_mapping_invalid_argument(self, served, pb2):
        channel, _, _ = served
        stub = _stub(channel, pb2)
        with pytest.raises(grpc.RpcError) as ei:
            stub.Allow(pb2.AllowRequest(key=""))
        assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        with pytest.raises(grpc.RpcError) as ei:
            stub.AllowN(pb2.AllowNRequest(key="k", n=0))
        assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT

    def test_error_mapping_unavailable_and_fail_open(self, pb2):
        clock = ManualClock(T0)
        cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=3,
                     window=60.0, fail_open=False)
        lim = create_limiter(cfg, backend="exact", clock=clock)
        srv = grpc_server_for_limiter(lim)
        srv.start()
        channel = grpc.insecure_channel(f"127.0.0.1:{srv.port}")
        stub = _stub(channel, pb2)
        try:
            lim.inject_failure()
            with pytest.raises(grpc.RpcError) as ei:
                stub.Allow(pb2.AllowRequest(key="k"))
            assert ei.value.code() == grpc.StatusCode.UNAVAILABLE
            lim.heal()
            assert stub.Allow(pb2.AllowRequest(key="k")).allowed
        finally:
            channel.close()
            srv.shutdown()
            lim.close()

    def test_fail_open_flag_carried(self, pb2):
        clock = ManualClock(T0)
        cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=3,
                     window=60.0, fail_open=True)
        lim = create_limiter(cfg, backend="exact", clock=clock)
        srv = grpc_server_for_limiter(lim)
        srv.start()
        channel = grpc.insecure_channel(f"127.0.0.1:{srv.port}")
        stub = _stub(channel, pb2)
        try:
            lim.inject_failure()
            resp = stub.Allow(pb2.AllowRequest(key="k"))
            assert resp.allowed and resp.fail_open
        finally:
            channel.close()
            srv.shutdown()
            lim.close()

    def test_allow_batch_single_bulk_submission(self, pb2):
        """Satellite pin: an N-item AllowBatch reaches the decide layer as
        ONE bulk submission (O(1) dispatches, not N sequential
        submit-wait round-trips), and results come back in request
        order."""
        calls = {"many": 0, "one": 0}

        def decide_many(pairs):
            calls["many"] += 1
            # Distinguishable per-item results to pin ordering.
            return [Result(allowed=(i % 2 == 0), limit=100, remaining=i,
                           retry_after=0.0, reset_at=T0)
                    for i, _ in enumerate(pairs)]

        def decide(key, n):
            calls["one"] += 1
            raise AssertionError("scalar path must not serve AllowBatch")

        srv = GrpcRateLimitServer(decide, lambda k: None,
                                  decide_many=decide_many)
        srv.start()
        channel = grpc.insecure_channel(f"127.0.0.1:{srv.port}")
        stub = _stub(channel, pb2)
        try:
            n_items = 64
            req = pb2.AllowBatchRequest(items=[
                pb2.AllowBatchRequest.Item(key=f"k{i}", n=1)
                for i in range(n_items)])
            out = stub.AllowBatch(req)
            assert calls == {"many": 1, "one": 0}
            assert [r.remaining for r in out.results] == list(range(n_items))
            assert [r.allowed for r in out.results] == [
                i % 2 == 0 for i in range(n_items)]
        finally:
            channel.close()
            srv.shutdown()

    def test_override_rpcs(self, pb2):
        """Set/Get/DeleteOverride change live decisions over gRPC."""
        clock = ManualClock(T0)
        cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=3, window=60.0)
        lim = create_limiter(cfg, backend="exact", clock=clock)
        srv = grpc_server_for_limiter(lim)
        srv.start()
        channel = grpc.insecure_channel(f"127.0.0.1:{srv.port}")
        stub = _stub(channel, pb2)
        try:
            resp = stub.GetOverride(pb2.GetOverrideRequest(key="vip"))
            assert not resp.found
            resp = stub.SetOverride(pb2.SetOverrideRequest(key="vip",
                                                           limit=7))
            assert resp.found and resp.limit == 7
            allowed = sum(stub.Allow(pb2.AllowRequest(key="vip")).allowed
                          for _ in range(9))
            assert allowed == 7
            assert stub.Allow(pb2.AllowRequest(key="std")).limit == 3
            resp = stub.GetOverride(pb2.GetOverrideRequest(key="vip"))
            assert resp.found and resp.limit == 7
            assert stub.DeleteOverride(
                pb2.DeleteOverrideRequest(key="vip")).deleted
            assert not stub.DeleteOverride(
                pb2.DeleteOverrideRequest(key="vip")).deleted
            with pytest.raises(grpc.RpcError) as ei:
                stub.SetOverride(pb2.SetOverrideRequest(key="v", limit=-4))
            assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        finally:
            channel.close()
            srv.shutdown()
            lim.close()

    def test_policy_mutations_journaled(self, pb2):
        """The gRPC door records the same control-plane journal events
        as the HTTP/binary doors (ADR-021): set-override /
        delete-override / reset, actor="grpc", hashed key tokens only."""
        import json

        from ratelimiter_tpu.observability import events

        clock = ManualClock(T0)
        cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=3,
                     window=60.0)
        lim = create_limiter(cfg, backend="exact", clock=clock)
        srv = grpc_server_for_limiter(lim)
        srv.start()
        channel = grpc.insecure_channel(f"127.0.0.1:{srv.port}")
        stub = _stub(channel, pb2)
        events.enable(capacity=64)
        try:
            stub.SetOverride(pb2.SetOverrideRequest(key="vip", limit=7))
            stub.DeleteOverride(pb2.DeleteOverrideRequest(key="vip"))
            stub.DeleteOverride(pb2.DeleteOverrideRequest(key="vip"))
            stub.Reset(pb2.ResetRequest(key="vip"))
            evs = events.get().tail(category="policy")["events"]
            assert [(e["action"], e["actor"]) for e in evs] == [
                ("set-override", "grpc"),
                ("delete-override", "grpc"),
                ("delete-override", "grpc"),
                ("reset", "grpc"),
            ]
            set_ev = evs[0]
            assert set_ev["payload"]["limit"] == 7
            assert set_ev["payload"]["window_scale"] == 1.0
            assert evs[1]["payload"]["deleted"] is True
            assert evs[2]["payload"]["deleted"] is False
            # Same hashed token at every mutation site; raw key absent.
            tokens = {e["payload"]["key_hash"] for e in evs}
            assert len(tokens) == 1
            assert "vip" not in json.dumps(evs)
        finally:
            events.disable()
            channel.close()
            srv.shutdown()
            lim.close()

    def test_tenant_crud_and_journal(self, pb2):
        """Tenant CRUD over gRPC: the registry mutations work and land
        in the control-plane journal with actor="grpc" — the same
        vocabulary as the HTTP twin's /v1/tenants (ADR-021), so an
        incident reconstruction never depends on WHICH surface the
        operator used."""
        import json

        from ratelimiter_tpu import HierarchySpec, SketchParams
        from ratelimiter_tpu.observability import events

        clock = ManualClock(T0)
        cfg = Config(
            algorithm=Algorithm.SLIDING_WINDOW, limit=50, window=60.0,
            sketch=SketchParams(depth=2, width=512, sub_windows=4),
            hierarchy=HierarchySpec(tenants=4))
        lim = create_limiter(cfg, backend="sketch", clock=clock)
        srv = GrpcRateLimitServer(
            lambda key, n: lim.allow_n(key, n), lim.reset,
            tenants=lim)
        srv.start()
        channel = grpc.insecure_channel(f"127.0.0.1:{srv.port}")
        stub = _stub(channel, pb2)
        events.enable(capacity=64)
        try:
            out = stub.SetTenant(pb2.SetTenantRequest(
                name="gold", limit=30, weight=3))
            assert out.found and out.limit == 30 and out.weight == 3
            assert out.floor == 3  # default: ceiling / 10
            got = stub.GetTenant(pb2.GetTenantRequest(name="gold"))
            assert got.found and got.tid == out.tid
            miss = stub.GetTenant(pb2.GetTenantRequest(name="nope"))
            assert not miss.found
            stub.AssignTenant(pb2.AssignTenantRequest(
                key="acct:1", tenant="gold"))
            assert lim.tenant_of("acct:1") == "gold"
            un = stub.UnassignTenant(pb2.UnassignTenantRequest(
                key="acct:1"))
            assert un.unassigned
            dl = stub.DeleteTenant(pb2.DeleteTenantRequest(name="gold"))
            assert dl.deleted
            assert not stub.DeleteTenant(
                pb2.DeleteTenantRequest(name="gold")).deleted
            # Unknown tenant on assign -> INVALID_ARGUMENT (core error
            # classes, same as every other surface).
            with pytest.raises(grpc.RpcError) as ei:
                stub.AssignTenant(pb2.AssignTenantRequest(
                    key="k", tenant="nope"))
            assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT

            evs = events.get().tail(category="tenant")["events"]
            assert [(e["action"], e["actor"]) for e in evs] == [
                ("set", "grpc"),
                ("assign", "grpc"),
                ("unassign", "grpc"),
                ("delete", "grpc"),
                ("delete", "grpc"),
            ]
            assert evs[0]["payload"] == {"name": "gold", "limit": 30,
                                         "weight": 3, "floor": 3}
            assert evs[3]["payload"]["deleted"] is True
            assert evs[4]["payload"]["deleted"] is False
            # Keys ride as hashed tokens only (OPERATIONS §6).
            assert "acct:1" not in json.dumps(evs)
            assert evs[1]["payload"]["key_hash"] == \
                evs[2]["payload"]["key_hash"]
        finally:
            events.disable()
            channel.close()
            srv.shutdown()
            lim.close()

    def test_tenantless_server_unimplemented(self, served, pb2):
        """Without a hierarchy surface the tenant RPCs are absent —
        UNIMPLEMENTED, exactly like any unregistered method."""
        channel, _lim, _clock = served
        stub = _stub(channel, pb2)
        with pytest.raises(grpc.RpcError) as ei:
            stub.SetTenant(pb2.SetTenantRequest(name="gold", limit=1))
        assert ei.value.code() == grpc.StatusCode.UNIMPLEMENTED

    def test_closed_limiter_failed_precondition(self, pb2):
        cfg = Config(algorithm=Algorithm.FIXED_WINDOW, limit=3, window=60.0)
        lim = create_limiter(cfg, backend="exact", clock=ManualClock(T0))
        srv = grpc_server_for_limiter(lim)
        srv.start()
        channel = grpc.insecure_channel(f"127.0.0.1:{srv.port}")
        stub = _stub(channel, pb2)
        try:
            lim.close()
            with pytest.raises(grpc.RpcError) as ei:
                stub.Allow(pb2.AllowRequest(key="k"))
            assert ei.value.code() == grpc.StatusCode.FAILED_PRECONDITION
        finally:
            channel.close()
            srv.shutdown()


class TestGrpcOnServerBinary:
    def test_grpc_alongside_binary_protocol(self):
        """--grpc-port on the real binary: gRPC and binary-protocol
        traffic share ONE limiter (quota consumed over gRPC is gone over
        the binary protocol too)."""
        import os
        import signal as sig
        import socket
        import subprocess
        import sys

        from ratelimiter_tpu.serving import Client

        pb2 = _load_pb2()
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
            + env.get("PYTHONPATH", "").split(os.pathsep))


        port, grpc_port = free_port(), free_port()
        proc = subprocess.Popen(
            [sys.executable, "-m", "ratelimiter_tpu.serving",
             "--backend", "exact", "--algorithm", "sliding_window",
             "--limit", "2", "--window", "60", "--port", str(port),
             "--grpc-port", str(grpc_port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        try:
            for _ in range(10):
                line = proc.stdout.readline()
                if line.startswith("serving"):
                    break
            assert "grpc:" in line, line
            channel = grpc.insecure_channel(f"127.0.0.1:{grpc_port}")
            stub = _stub(channel, pb2)
            assert stub.Allow(pb2.AllowRequest(key="shared")).allowed
            with Client(port=port, timeout=10.0) as c:
                assert c.allow("shared").allowed       # 2 of 2 used
                assert not c.allow("shared").allowed
            assert not stub.Allow(pb2.AllowRequest(key="shared")).allowed
            channel.close()
            proc.send_signal(sig.SIGTERM)
            assert proc.wait(timeout=15) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()

    def test_grpc_on_native_sharded_door(self):
        """--native --shards 2 --grpc-port: gRPC decisions route through
        the same FNV shard router as binary traffic, so one key has ONE
        quota across both surfaces (the ADVICE r4 composition fix,
        exercised end to end on the real binary)."""
        import os
        import signal as sig
        import subprocess
        import sys

        from ratelimiter_tpu.serving import Client
        from ratelimiter_tpu.serving.native_server import (
            native_server_available,
        )

        if not native_server_available():
            pytest.skip("needs g++ for the native server")
        pb2 = _load_pb2()
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
            + env.get("PYTHONPATH", "").split(os.pathsep))
        env["JAX_PLATFORMS"] = "cpu"

        port, grpc_port = free_port(), free_port()
        proc = subprocess.Popen(
            [sys.executable, "-m", "ratelimiter_tpu.serving",
             "--backend", "sketch", "--algorithm", "sliding_window",
             "--limit", "4", "--window", "60",
             "--sketch-depth", "3", "--sketch-width", "256",
             "--no-prewarm", "--native", "--shards", "2",
             "--port", str(port), "--grpc-port", str(grpc_port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        try:
            for _ in range(20):
                line = proc.stdout.readline()
                if line.startswith("serving"):
                    break
            assert "grpc:" in line, line
            channel = grpc.insecure_channel(f"127.0.0.1:{grpc_port}")
            stub = _stub(channel, pb2)
            with Client(port=port, timeout=30.0) as c:
                # Keys spanning both shards; half the quota per surface.
                for k in ("mix0", "mix1", "mix2", "mix3"):
                    assert c.allow_n(k, 2).allowed
                    assert stub.AllowN(
                        pb2.AllowNRequest(key=k, n=2)).allowed
                    assert not c.allow(k).allowed          # binary sees 4/4
                    assert not stub.Allow(
                        pb2.AllowRequest(key=k)).allowed   # so does gRPC
                    stub.Reset(pb2.ResetRequest(key=k))    # routed reset
                    assert c.allow(k).allowed
            channel.close()
            proc.send_signal(sig.SIGTERM)
            assert proc.wait(timeout=15) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
