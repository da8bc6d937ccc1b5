"""Socket layer: envelope codecs, the TCP registry server, and the socket
transport's error/streaming/pooling behavior.

Transport *conformance* (socket moves the same chunks as local/wire, byte
relations, plan quoting) lives in ``tests/test_transport.py``; this file
covers the protocol pieces themselves.
"""

import threading
import time

import pytest

from repro.core import cdc, hashing
from repro.core.cdmt import CDMTParams
from repro.core.errors import DeliveryError
from repro.core.registry import PushRejected, Registry
from repro.delivery import (ImageClient, LocalTransport, RegistryServer,
                            SocketRegistryServer, SocketTransport, wire)

PARAMS = cdc.CDCParams(mask_bits=10, min_size=128, max_size=8192)
P = CDMTParams(window=4, rule_bits=2)


def _rand(n, seed=0):
    import numpy as np
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.uint8).tobytes()


def _seeded_server(n_versions=3, seed=70, **server_kw):
    import numpy as np
    rng = np.random.default_rng(seed)
    data = bytearray(_rand(120_000, seed))
    reg = Registry(cdmt_params=P)
    pub = ImageClient(LocalTransport(reg), cdc_params=PARAMS, cdmt_params=P)
    versions = []
    for i in range(n_versions):
        versions.append(bytes(data))
        pub.commit("app", f"v{i}", bytes(data))
        pub.push("app", f"v{i}")
        pos = int(rng.integers(0, len(data) - 200))
        data[pos:pos + 128] = rng.bytes(128)
        ins = int(rng.integers(0, len(data)))
        data[ins:ins] = rng.bytes(64)
    return RegistryServer(reg, **server_kw), versions


# ---------------------------------------------------------------- codecs


class TestEnvelopeCodecs:
    def test_request_roundtrip(self):
        frames = [wire.encode_want([hashing.chunk_fingerprint(b"x")]),
                  b"\x00" * 17]
        buf = wire.encode_request(wire.Op.WANT, "app", "v3", frames)
        op, lineage, tag, out = wire.decode_request(buf)
        assert (op, lineage, tag, out) == (wire.Op.WANT, "app", "v3", frames)

    def test_request_no_frames_and_unicode_routing(self):
        buf = wire.encode_request(wire.Op.INDEX, "appé", "v∞")
        op, lineage, tag, out = wire.decode_request(buf)
        assert (op, lineage, tag, out) == (wire.Op.INDEX, "appé", "v∞", [])

    def test_request_bad_magic_version_op_truncation(self):
        buf = wire.encode_request(wire.Op.HAS, "a", "b", [b"xy"])
        with pytest.raises(wire.WireError):
            wire.decode_request(b"XX" + buf[2:])
        with pytest.raises(wire.WireError):
            wire.decode_request(buf[:2] + b"\x99" + buf[3:])
        with pytest.raises(wire.WireError):
            wire.decode_request(buf[:3] + b"\xfe" + buf[4:])   # unknown op
        with pytest.raises(wire.WireError):
            wire.decode_request(buf[:-1])                      # truncated
        with pytest.raises(wire.WireError):
            wire.decode_request(buf + b"!")                    # trailing

    def test_response_roundtrip_and_error_status(self):
        frames = [b"alpha", b"", b"gamma"]
        status, out = wire.decode_response(
            wire.encode_response(wire.STATUS_OK, frames))
        assert (status, out) == (wire.STATUS_OK, frames)
        err = wire.encode_error(wire.ErrorCode.DELIVERY, "nope")
        status, out = wire.decode_response(
            wire.encode_response(wire.STATUS_ERROR, [err]))
        assert status == wire.STATUS_ERROR
        assert wire.decode_error(out[0]) == (wire.ErrorCode.DELIVERY, "nope")

    def test_envelope_sizing_is_exact(self):
        frames = [b"x" * n for n in (0, 1, 127, 128, 300)]
        lens = [len(f) for f in frames]
        assert wire.request_envelope_bytes("lineage", "tag", lens) \
            == len(wire.encode_request(wire.Op.PUSH, "lineage", "tag",
                                       frames))
        assert wire.response_envelope_bytes(lens) \
            == len(wire.encode_response(wire.STATUS_OK, frames))

    def test_chunk_batch_frame_lens_match_sum(self):
        sizes = [100, 2000, 1, 0, 550, 129]
        lens = wire.chunk_batch_frame_lens(sizes, 2)
        assert len(lens) == 3
        assert sum(lens) == wire.chunk_batches_wire_bytes(sizes, 2)


class TestControlFrames:
    def test_tags_roundtrip(self):
        assert wire.decode_tags_request(wire.encode_tags_request("app")) \
            == "app"
        tags = ["v0", "release-1.2", "head"]
        assert wire.decode_tag_list(wire.encode_tag_list(tags)) == tags
        assert wire.decode_tag_list(wire.encode_tag_list([])) == []
        with pytest.raises(wire.WireError):
            wire.decode_tag_list(wire.encode_tags_request("app"))

    def test_error_roundtrip_and_unknown_code_degrades(self):
        for code in wire.ErrorCode:
            assert wire.decode_error(wire.encode_error(code, "m")) \
                == (code, "m")
        # a future error code decodes as INTERNAL instead of raising
        raw = wire.encode_frame(
            wire.FrameType.ERROR,
            wire.encode_uvarint(250) + wire.encode_uvarint(2) + b"hi")
        assert wire.decode_error(raw) == (wire.ErrorCode.INTERNAL, "hi")

    def test_receipt_roundtrip(self):
        from repro.core.registry import PushReceipt
        r = PushReceipt(lineage="app", tag="v3", version=3,
                        chunks_received=17, bytes_received=54321,
                        index_bytes=900, root=hashing.chunk_fingerprint(b"r"),
                        nodes_created=5, nodes_hashed=9, hash_calls=40,
                        deduplicated=True)
        assert wire.decode_receipt(wire.encode_receipt(r)) == r
        with pytest.raises(wire.WireError):
            wire.decode_receipt(wire.encode_receipt(r)[:-1])

    def test_receipt_roundtrip_empty_artifact(self):
        """An empty artifact's receipt carries root=None (its CDMT has no
        root) — the frame must encode the absence, not crash."""
        from repro.core.registry import PushReceipt
        r = PushReceipt(lineage="app", tag="v0", version=0,
                        chunks_received=0, bytes_received=0,
                        index_bytes=0, root=None)
        assert wire.decode_receipt(wire.encode_receipt(r)) == r

    def test_info_roundtrip(self):
        assert wire.decode_info(wire.encode_info(64)) == 64


# ------------------------------------------------------------ socket server


@pytest.fixture()
def sock_env():
    srv, versions = _seeded_server()
    sock_srv = SocketRegistryServer(srv)
    transports = []

    def connect(**kw):
        t = SocketTransport(sock_srv.address, **kw)
        transports.append(t)
        return t

    yield srv, sock_srv, versions, connect
    for t in transports:
        t.close()
    sock_srv.stop()


def _metered_egress(sock_srv, want, timeout=5.0):
    """The server's stats once its egress meter reads ``want`` (or at the
    deadline).  The server meters a write after ``sendall`` returns, which
    can be after the client has read the whole response."""
    deadline = time.monotonic() + timeout
    s = sock_srv.snapshot()
    while s.egress_bytes != want and time.monotonic() < deadline:
        time.sleep(0.005)
        s = sock_srv.snapshot()
    return s


class TestSocketServer:
    def test_pull_and_materialize(self, sock_env):
        srv, sock_srv, versions, connect = sock_env
        cl = ImageClient(connect(), cdc_params=PARAMS, cdmt_params=P)
        rep = cl.pull("app", "v2")
        assert cl.materialize("app", "v2") == versions[2]
        assert rep.transport == "socket"
        assert rep.chunks_moved == rep.chunks_total

    def test_streamed_want_multi_frame(self, sock_env):
        """A WANT larger than the server's batch split comes back as several
        CHUNK_BATCH frames inside one response — one round, many frames."""
        srv, sock_srv, versions, connect = sock_env
        t = connect(batch_chunks=1024)
        cl = ImageClient(t, cdc_params=PARAMS, cdmt_params=P,
                         batch_chunks=1024)
        plan = cl.plan_pull("app", "v0")
        assert plan.chunks_to_fetch > srv.max_batch_chunks
        rep = cl.execute(plan)
        leg = rep.sources["registry"]
        assert leg.rounds == 1                    # one request round-trip…
        assert rep.chunks_moved == plan.chunks_to_fetch
        # …whose framing matched the server's split exactly, per the quote
        assert (rep.index_bytes + rep.recipe_bytes + rep.chunk_bytes) \
            == plan.expected_wire_bytes

    def test_envelope_overhead_identity_on_index(self, sock_env):
        """Socket meters == frame meters + exactly the envelope bytes."""
        srv, sock_srv, versions, connect = sock_env
        t = connect()
        info = len(wire.encode_info(srv.max_batch_chunks))
        s0 = _metered_egress(sock_srv, wire.response_envelope_bytes([info]))
        f0 = srv.snapshot()
        idx, nbytes = t.get_index("app", "v1")
        f1 = srv.snapshot()
        frame_len = f1.egress_bytes - f0.egress_bytes
        req_len = wire.request_envelope_bytes("app", "v1", [])
        want_egress = wire.response_envelope_bytes([frame_len])
        s1 = _metered_egress(sock_srv, s0.egress_bytes + want_egress)
        assert s1.ingress_bytes - s0.ingress_bytes == req_len
        assert s1.egress_bytes - s0.egress_bytes == want_egress
        assert nbytes == req_len + wire.response_envelope_bytes([frame_len])

    def test_tags_over_socket_metered(self, sock_env):
        srv, sock_srv, versions, connect = sock_env
        t = connect()
        f0 = srv.snapshot()
        assert t.tags("app") == ["v0", "v1", "v2"]
        f1 = srv.snapshot()
        assert f1.tags_requests == f0.tags_requests + 1
        assert f1.ingress_bytes > f0.ingress_bytes
        assert f1.egress_bytes > f0.egress_bytes

    def test_remote_errors_reraise_matching_exceptions(self, sock_env):
        srv, sock_srv, versions, connect = sock_env
        t = connect()
        cl = ImageClient(t, cdc_params=PARAMS, cdmt_params=P)
        with pytest.raises(DeliveryError):
            cl.pull("ghost", "v0")             # unknown lineage
        with pytest.raises(DeliveryError):
            cl.pull("app", "v99")              # unknown tag
        # a push whose claimed root is a lie is rejected server-side and
        # re-raised client-side as PushRejected, not a generic failure
        cl.commit("b", "v0", _rand(40_000, seed=71))
        real_index_for_tag = cl.index_for_tag

        def lying(lineage, tag):
            import copy
            forged = copy.copy(real_index_for_tag(lineage, tag))
            forged.root = hashing.chunk_fingerprint(b"forged")
            return forged

        cl.index_for_tag = lying
        with pytest.raises(PushRejected):
            cl.push("b", "v0")

    def test_garbage_envelope_gets_error_reply_then_close(self, sock_env):
        """A client speaking the wrong protocol gets one ERROR frame and a
        closed connection — the server neither crashes a thread nor hangs,
        and keeps serving real clients."""
        import socket as socket_mod
        srv, sock_srv, versions, connect = sock_env
        s = socket_mod.create_connection(sock_srv.address)
        s.sendall(b"GET / HTTP/1.1\r\n\r\n")
        s.settimeout(5)
        status, frames = wire.decode_response(s.recv(4096))
        assert status == wire.STATUS_ERROR
        code, _msg = wire.decode_error(frames[0])
        assert code is wire.ErrorCode.WIRE
        assert s.recv(100) == b""              # connection closed after
        s.close()
        assert sock_srv.snapshot().errors >= 1
        cl = ImageClient(connect(), cdc_params=PARAMS, cdmt_params=P)
        cl.pull("app", "v1")
        assert cl.materialize("app", "v1") == versions[1]

    def test_malformed_body_frame_is_wire_error(self, sock_env):
        srv, sock_srv, versions, connect = sock_env
        t = connect()
        with pytest.raises(wire.WireError):
            t._exchange(wire.Op.WANT, "app", "v0", [b"garbage-not-a-frame"])

    def test_connection_refused_is_delivery_error(self, sock_env):
        srv, sock_srv, versions, connect = sock_env
        host, port = sock_srv.address
        sock_srv.stop()
        with pytest.raises(DeliveryError):
            SocketTransport((host, port), timeout=2.0)

    def test_push_roundtrip_receipt(self, sock_env):
        srv, sock_srv, versions, connect = sock_env
        t = connect()
        cl = ImageClient(t, cdc_params=PARAMS, cdmt_params=P)
        data = _rand(60_000, seed=72)
        cl.commit("fresh", "v0", data)
        rep = cl.push("fresh", "v0")
        assert rep.chunks_moved > 0
        puller = ImageClient(connect(), cdc_params=PARAMS, cdmt_params=P)
        puller.pull("fresh", "v0")
        assert puller.materialize("fresh", "v0") == data

    def test_empty_artifact_over_socket(self, sock_env):
        srv, sock_srv, versions, connect = sock_env
        cl = ImageClient(connect(), cdc_params=PARAMS, cdmt_params=P)
        cl.commit("empty", "v0", b"")
        cl.push("empty", "v0")
        puller = ImageClient(connect(), cdc_params=PARAMS, cdmt_params=P)
        puller.pull("empty", "v0")
        assert puller.materialize("empty", "v0") == b""

    def test_concurrent_pullers_share_server(self, sock_env):
        srv, sock_srv, versions, connect = sock_env
        n = 4
        clients = [ImageClient(connect(), cdc_params=PARAMS, cdmt_params=P)
                   for _ in range(n)]
        errors = []

        def pull(cl):
            try:
                cl.pull("app", "v2")
            except BaseException as e:   # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=pull, args=(c,)) for c in clients]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errors
        for cl in clients:
            assert cl.materialize("app", "v2") == versions[2]

    def test_connection_pool_reuses_sockets(self, sock_env):
        srv, sock_srv, versions, connect = sock_env
        t = connect()
        cl = ImageClient(t, cdc_params=PARAMS, cdmt_params=P,
                         pipeline_depth=1)
        cl.pull("app", "v0")
        cl.pull("app", "v1")
        cl.pull("app", "v2")
        # sequential traffic rides one pooled connection (plus none extra)
        assert sock_srv.snapshot().connections == 1

    def test_stalled_mid_request_client_is_dropped(self):
        """A client that starts a request and stalls must not pin a server
        connection thread forever — after ``io_timeout`` the server drops
        the connection (idle *between* requests is separately bounded by
        ``idle_timeout`` when configured; pooled clients survive that reap
        via the stale-connection retry)."""
        import socket as socket_mod
        srv, _versions = _seeded_server()
        sock_srv = SocketRegistryServer(srv, io_timeout=0.5)
        try:
            s = socket_mod.create_connection(sock_srv.address)
            s.sendall(wire.REQUEST_MAGIC)      # request started, then stall
            s.settimeout(5)
            assert s.recv(100) == b""          # server gave up and closed
            s.close()
            # the server is healthy and still answers real clients
            t = SocketTransport(sock_srv.address)
            assert t.tags("app") == ["v0", "v1", "v2"]
            t.close()
        finally:
            sock_srv.stop()

    def test_oversized_length_prefix_rejected_before_allocation(self,
                                                                sock_env):
        srv, sock_srv, versions, connect = sock_env
        import socket as socket_mod
        s = socket_mod.create_connection(sock_srv.address)
        # op INDEX, then a lineage length prefix claiming ~2^35 bytes
        s.sendall(wire.REQUEST_MAGIC + bytes((wire.VERSION, wire.Op.INDEX))
                  + wire.encode_uvarint(1 << 35))
        s.settimeout(5)
        status, frames = wire.decode_response(s.recv(4096))
        assert status == wire.STATUS_ERROR
        code, msg = wire.decode_error(frames[0])
        assert code is wire.ErrorCode.WIRE
        assert "exceeds" in msg
        s.close()

    def test_closed_transport_refuses(self, sock_env):
        srv, sock_srv, versions, connect = sock_env
        t = connect()
        t.close()
        with pytest.raises(DeliveryError):
            t.tags("app")
