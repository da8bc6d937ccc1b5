"""Async data plane: mux envelope codecs, the event-loop server's
concurrency behavior (fairness, backpressure, admission control, idle
reaping), and the satellite pool-hygiene fixes on the threaded transport.

Byte-exactness and transport conformance for the mux transport live in
``tests/test_transport.py`` (the matrix runs every transport through the
same scenario); this file covers what is *new* with the event loop.
"""

import socket as socket_mod
import threading
import time

import pytest

from repro.core import cdc
from repro.core.cdmt import CDMTParams
from repro.core.errors import DeliveryError
from repro.core.registry import Registry
from repro.delivery import (AsyncRegistryServer, ImageClient, LocalTransport,
                            MuxSocketTransport, RegistryServer,
                            SocketRegistryServer, SocketTransport,
                            serve_registry_async, wire)

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


# ----------------------------------------------------------------- codecs


class TestMuxCodecs:
    def test_request_roundtrip(self):
        frames = [b"alpha", b"", b"x" * 300]
        buf = wire.encode_mux_request(wire.Op.WANT, 7, "lin", "tag", frames)
        assert wire.decode_mux_request(buf) == (
            wire.Op.WANT, 7, "lin", "tag", frames)

    def test_request_stream_id_is_fixed_width(self):
        """Envelope size must not depend on the stream id value — that is
        what keeps plan quotes exact without knowing future ids."""
        a = wire.encode_mux_request(wire.Op.INDEX, 0, "l", "t")
        b = wire.encode_mux_request(wire.Op.INDEX, wire.MAX_STREAM_ID,
                                    "l", "t")
        assert len(a) == len(b)

    def test_request_stream_id_out_of_range(self):
        with pytest.raises(wire.WireError):
            wire.encode_mux_request(wire.Op.INDEX, wire.MAX_STREAM_ID + 1,
                                    "l", "t")

    def test_response_header_and_frame_roundtrip(self):
        hdr = wire.encode_mux_response_header(9, wire.STATUS_OK, 3)
        sid, status, n, off = wire.decode_mux_response_header(hdr)
        assert (sid, status, n, off) == (9, wire.STATUS_OK, 3, len(hdr))
        msg = wire.encode_mux_response_frame(9, b"payload")
        sid, frame, off = wire.decode_mux_response_frame(msg)
        assert (sid, frame, off) == (9, b"payload", len(msg))

    def test_header_frame_confusion_rejected(self):
        hdr = wire.encode_mux_response_header(1, wire.STATUS_OK, 0)
        with pytest.raises(wire.WireError):
            wire.decode_mux_response_frame(hdr)
        msg = wire.encode_mux_response_frame(1, b"x")
        with pytest.raises(wire.WireError):
            wire.decode_mux_response_header(msg)

    def test_bad_magic_and_version_rejected(self):
        with pytest.raises(wire.WireError):
            wire.check_mux_request_header(b"XX\x01\x01\x00\x00\x00\x01")
        with pytest.raises(wire.WireError):
            wire.check_mux_response_header(b"CS\x63\x00\x00\x00\x00\x01")

    def test_sizing_identities_match_encoders(self):
        frames = [b"a" * 5, b"b" * 1000]
        req = wire.encode_mux_request(wire.Op.PUSH, 3, "lin", "t2", frames)
        assert len(req) == wire.mux_request_envelope_bytes(
            "lin", "t2", [len(f) for f in frames])
        lens = [17, 0, 4096]
        measured = len(wire.encode_mux_response_header(5, wire.STATUS_OK,
                                                       len(lens)))
        for n in lens:
            measured += len(wire.encode_mux_response_frame(5, b"z" * n))
        assert measured == wire.mux_response_envelope_bytes(lens)

    def test_busy_error_code_roundtrip(self):
        frame = wire.encode_error(wire.ErrorCode.BUSY, "overloaded")
        assert wire.decode_error(frame) == (wire.ErrorCode.BUSY,
                                            "overloaded")


# ----------------------------------------------------------------- server


@pytest.fixture()
def aio_env():
    srv, versions = _seeded_server()
    asrv = AsyncRegistryServer(srv)
    transports = []

    def connect(**kw):
        t = MuxSocketTransport(asrv.address, **kw)
        transports.append(t)
        return t

    yield srv, asrv, versions, connect
    for t in transports:
        t.close()
    asrv.stop()


class TestAsyncServer:
    def test_pull_and_materialize(self, aio_env):
        srv, asrv, versions, connect = aio_env
        cl = ImageClient(connect(), cdc_params=PARAMS, cdmt_params=P)
        rep = cl.pull("app", "v2")
        assert cl.materialize("app", "v2") == versions[2]
        assert rep.transport == "mux"
        assert rep.chunks_moved == rep.chunks_total

    def test_o_cores_threads_regardless_of_clients(self, aio_env):
        """The scale claim: thread count is fixed at construction — loop +
        worker pool — and does not grow with connections."""
        srv, asrv, versions, connect = aio_env
        assert asrv.thread_count == 1 + asrv.workers
        before = threading.active_count()
        clients = [ImageClient(connect(), cdc_params=PARAMS, cdmt_params=P)
                   for _ in range(8)]
        for cl in clients:
            cl.pull("app", "v1")
        # each transport adds its own reader threads and the server's lazy
        # worker pool fills up to its fixed cap — nothing grows per client
        grown = threading.active_count() - before
        assert grown <= asrv.workers + sum(
            len(cl.transport._conns) for cl in clients)

    def test_many_concurrent_pullers_one_connection_each(self, aio_env):
        srv, asrv, versions, connect = aio_env
        errors = []

        def puller(i):
            try:
                cl = ImageClient(connect(connections=1),
                                 cdc_params=PARAMS, cdmt_params=P)
                cl.pull("app", f"v{i % 3}")
                assert cl.materialize("app", f"v{i % 3}") == versions[i % 3]
            except Exception as e:          # noqa: BLE001 — collected
                errors.append(e)

        threads = [threading.Thread(target=puller, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors

    def test_concurrent_streams_share_one_transport(self, aio_env):
        """Many threads multiplex over one shared transport's few
        connections — the per-stream demux must never cross wires."""
        srv, asrv, versions, connect = aio_env
        transport = connect(connections=2)
        errors = []

        def worker(i):
            try:
                idx, _ = transport.get_index("app", f"v{i % 3}")
                recipe, _ = transport.get_recipe("app", f"v{i % 3}")
                assert len(idx.leaf_fps()) == len(recipe.fps)
            except Exception as e:          # noqa: BLE001 — collected
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors
        assert len(transport._conns) <= 2

    def test_admission_control_sheds_with_busy(self):
        """Past ``max_inflight`` the server answers BUSY instead of
        queueing — typed, immediate, and counted."""
        srv, _versions = _seeded_server()
        # workers=1 + a stalled handler ⇒ the next requests stay in flight
        asrv = AsyncRegistryServer(srv, workers=1, max_inflight=1)
        gate = threading.Event()
        real = srv.get_index

        def slow_get_index(lineage, tag):
            gate.wait(timeout=30)
            return real(lineage, tag)

        srv.get_index = slow_get_index
        t = MuxSocketTransport(asrv.address)
        try:
            blocker = threading.Thread(
                target=lambda: t.get_index("app", "v0"), daemon=True)
            blocker.start()
            deadline = time.monotonic() + 10
            while (srv.metrics.snapshot().value(
                    "async_inflight_requests", {}) < 1
                    and time.monotonic() < deadline):
                time.sleep(0.01)             # wait for admission
            with pytest.raises(DeliveryError, match="busy"):
                t.get_index("app", "v1")     # overlaps the blocker → BUSY
            gate.set()
            blocker.join(timeout=30)
            assert asrv.stats.sheds >= 1
            snap = srv.metrics.snapshot()
            assert snap.value("async_shed_total", {}) >= 1
        finally:
            gate.set()
            t.close()
            asrv.stop()

    @pytest.mark.parametrize("workers", [1, 2])
    def test_queue_wait_counts_time_behind_busy_workers(self, workers):
        """Two slow requests at once: with one worker the second waits for
        the first's run, with two neither waits; either way the job
        counters read through a scrape."""
        srv, _versions = _seeded_server()
        asrv = AsyncRegistryServer(srv, workers=workers)
        real = srv.get_index
        slow = 0.4

        def slow_get_index(lineage, tag):
            time.sleep(slow)
            return real(lineage, tag)

        srv.get_index = slow_get_index
        t = MuxSocketTransport(asrv.address)
        try:
            before = t.scrape_metrics()
            threads = [threading.Thread(target=t.get_index,
                                        args=("app", f"v{i}"))
                       for i in range(2)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=30)
            assert not any(th.is_alive() for th in threads)
            after = t.scrape_metrics()
        finally:
            t.close()
            asrv.stop()

        def delta(name):
            return after.value(name, {}) - before.value(name, {})

        assert delta("async_jobs_total") >= 2
        assert delta("async_job_seconds_total") >= 2 * slow * 0.9
        if workers == 1:
            assert delta("async_queue_wait_seconds_total") >= slow / 2
        else:
            assert delta("async_queue_wait_seconds_total") < slow / 2

    def test_idle_reap_and_transparent_redial(self):
        """The server reaps a connection idle between requests; the shared
        mux connection redials on next use instead of failing the call."""
        srv, _versions = _seeded_server()
        asrv = AsyncRegistryServer(srv, idle_timeout=0.2)
        t = MuxSocketTransport(asrv.address, connections=1)
        try:
            t.get_index("app", "v0")
            deadline = time.monotonic() + 10
            while (srv.metrics.snapshot().value(
                    "async_idle_reaped_total", {}) < 1
                    and time.monotonic() < deadline):
                time.sleep(0.05)
            assert srv.metrics.snapshot().value(
                "async_idle_reaped_total", {}) >= 1
            # the reaped socket is still in the transport; next call must
            # succeed anyway (stale-stream retry on a fresh connection)
            idx, _ = t.get_index("app", "v1")
            assert len(idx.leaf_fps()) > 0
        finally:
            t.close()
            asrv.stop()

    def test_mux_error_maps_to_typed_exception(self, aio_env):
        srv, asrv, versions, connect = aio_env
        t = connect()
        with pytest.raises(DeliveryError):
            t.get_index("app", "no-such-tag")
        # the connection survives a typed error (no close, no redial)
        idx, _ = t.get_index("app", "v0")
        assert len(idx.leaf_fps()) > 0
        assert asrv.stats.errors >= 1

    def test_plain_envelope_client_is_rejected(self, aio_env):
        """The async server speaks only the mux protocol; a plain-envelope
        ("CQ") client must be dropped, not answered garbage."""
        srv, asrv, versions, connect = aio_env
        s = socket_mod.create_connection(asrv.address)
        try:
            s.sendall(wire.encode_request(wire.Op.INDEX, "app", "v0"))
            s.settimeout(10)
            assert s.recv(100) == b""        # server closed on bad magic
        finally:
            s.close()

    def test_stop_is_idempotent_and_releases_port(self):
        srv, _versions = _seeded_server()
        asrv = AsyncRegistryServer(srv)
        addr = asrv.address
        asrv.stop()
        asrv.stop()                          # second stop is a no-op
        with pytest.raises(DeliveryError):
            MuxSocketTransport(addr, timeout=0.5)

    def test_scrape_metrics_over_mux(self, aio_env):
        srv, asrv, versions, connect = aio_env
        t = connect()
        cl = ImageClient(t, cdc_params=PARAMS, cdmt_params=P)
        cl.pull("app", "v2")
        scraped = t.scrape_metrics()
        local = srv.metrics.snapshot()
        assert scraped.value("registry_requests_total", {"op": "want"}) \
            == local.value("registry_requests_total", {"op": "want"})
        assert scraped.value("async_requests_total", {}) >= 1
        assert scraped.value("async_open_connections", {}) >= 1


class TestFairness:
    def test_small_pulls_not_starved_by_large_pull(self):
        """One huge WANT stream must not starve many small pulls: handler
        work is scheduled per CHUNK_BATCH, so small streams interleave.
        Scaled-down fairness gate: every small pull (a few chunks) must
        finish while the large stream (hundreds of chunks, small server
        split ⇒ hundreds of scheduling points) is still running, and their
        p99 stays bounded."""
        import numpy as np
        rng = np.random.default_rng(73)
        reg = Registry(cdmt_params=P)
        pub = ImageClient(LocalTransport(reg), cdc_params=PARAMS,
                          cdmt_params=P)
        pub.commit("big", "v0", _rand(600_000, seed=74))
        pub.push("big", "v0")
        pub.commit("small", "v0", _rand(4_000, seed=75))
        pub.push("small", "v0")
        srv = RegistryServer(reg, max_batch_chunks=4)
        # pace the big stream like a store with per-batch latency, so the
        # interleaving window is real on localhost (~190 batches ⇒ ≥ 1s)
        real_want_plan = srv.want_plan

        def paced_want_plan(want_frame):
            n, frames = real_want_plan(want_frame)

            def paced():
                for f in frames:
                    time.sleep(0.005)
                    yield f

            return n, paced()

        srv.want_plan = paced_want_plan
        asrv = AsyncRegistryServer(srv, workers=2)
        transport = MuxSocketTransport(asrv.address, connections=2)
        try:
            big_done = threading.Event()
            lat = []
            errors = []

            def big_pull():
                cl = ImageClient(MuxSocketTransport(asrv.address),
                                 cdc_params=PARAMS, cdmt_params=P)
                try:
                    cl.pull("big", "v0")
                except Exception as e:      # noqa: BLE001 — collected
                    errors.append(e)
                finally:
                    big_done.set()
                    cl.transport.close()

            def small_pull():
                try:
                    t0 = time.perf_counter()
                    idx, _ = transport.get_index("small", "v0")
                    recipe, _ = transport.get_recipe("small", "v0")
                    res = transport.fetch_chunks("small", "v0", recipe.fps)
                    lat.append(time.perf_counter() - t0)
                    assert len(res.chunks) == len(set(recipe.fps))
                    assert not big_done.is_set(), \
                        "small pull outlived the large pull"
                except Exception as e:      # noqa: BLE001 — collected
                    errors.append(e)

            big = threading.Thread(target=big_pull)
            big.start()
            time.sleep(0.05)                 # let the big stream get going
            smalls = [threading.Thread(target=small_pull)
                      for _ in range(12)]
            for t in smalls:
                t.start()
            for t in smalls:
                t.join(timeout=60)
            big.join(timeout=60)
            assert not errors
            assert len(lat) == 12
            # generous absolute bound: each small pull is 3 tiny
            # exchanges; starvation behind a ~200-batch stream would blow
            # straight past this
            assert sorted(lat)[-1] < 5.0
        finally:
            transport.close()
            asrv.stop()


# ------------------------------------------------- threaded-server satellites


class TestThreadedIdleReap:
    def test_server_reaps_idle_connection(self):
        srv, _versions = _seeded_server()
        sock_srv = SocketRegistryServer(srv, idle_timeout=0.2)
        try:
            t = SocketTransport(sock_srv.address)
            t.get_index("app", "v0")
            deadline = time.monotonic() + 10
            while (srv.metrics.snapshot().value(
                    "socket_idle_reaped_total", {}) < 1
                    and time.monotonic() < deadline):
                time.sleep(0.05)
            assert srv.metrics.snapshot().value(
                "socket_idle_reaped_total", {}) >= 1
            # graceful eviction: the pooled socket was reaped server-side,
            # yet the next exchange succeeds via the stale-conn retry
            idx, _ = t.get_index("app", "v1")
            assert len(idx.leaf_fps()) > 0
            t.close()
        finally:
            sock_srv.stop()

    def test_no_reaping_by_default(self):
        """``idle_timeout=None`` preserves the historical contract: a
        pooled connection may idle past any io_timeout and still serve."""
        srv, _versions = _seeded_server()
        sock_srv = SocketRegistryServer(srv, io_timeout=0.3)
        try:
            t = SocketTransport(sock_srv.address)
            t.get_index("app", "v0")
            time.sleep(0.6)                  # > io_timeout, idle is exempt
            idx, _ = t.get_index("app", "v1")
            assert len(idx.leaf_fps()) > 0
            assert srv.metrics.snapshot().value(
                "socket_idle_reaped_total", {}) == 0
            t.close()
        finally:
            sock_srv.stop()


class TestPoolHygiene:
    def test_pool_bounded_and_gauged(self):
        srv, _versions = _seeded_server()
        sock_srv = SocketRegistryServer(srv)
        try:
            t = SocketTransport(sock_srv.address, pool_size=2)
            conns = [t._checkout() for _ in range(5)]
            for c in conns:
                t._checkin(c)
            assert len(t._pool) == 2         # excess checkins closed
            assert t.metrics.snapshot().value(
                "transport_pool_connections",
                {"transport": "socket"}) == 2
            t.close()
            assert t.metrics.snapshot().value(
                "transport_pool_connections",
                {"transport": "socket"}) == 0
        finally:
            sock_srv.stop()

    def test_ttl_expired_connection_not_reused(self):
        srv, _versions = _seeded_server()
        sock_srv = SocketRegistryServer(srv)
        try:
            t = SocketTransport(sock_srv.address, pool_ttl=0.05)
            t.get_index("app", "v0")
            assert len(t._pool) == 1
            expired = t._pool[0]
            time.sleep(0.1)
            idx, _ = t.get_index("app", "v1")   # dials fresh, works
            assert len(idx.leaf_fps()) > 0
            assert expired.sock.fileno() == -1  # TTL victim was closed
            t.close()
        finally:
            sock_srv.stop()

    def test_restarted_server_does_not_fail_pooled_client(self):
        """Server restart while a client connection sits in the pool: the
        first reuse must redial, not surface DeliveryError."""
        srv, _versions = _seeded_server()
        sock_srv = SocketRegistryServer(srv)
        t = SocketTransport(sock_srv.address)
        try:
            t.get_index("app", "v0")
            assert len(t._pool) == 1
            host, port = sock_srv.address
            sock_srv.stop()                  # pooled conn is now dead
            sock_srv = SocketRegistryServer(srv, host=host, port=port)
            idx, _ = t.get_index("app", "v1")
            assert len(idx.leaf_fps()) > 0
        finally:
            t.close()
            sock_srv.stop()

    def test_fresh_connection_failure_still_raises(self):
        """The retry is for *reused* connections only — a first-dial
        failure surfaces immediately."""
        srv, _versions = _seeded_server()
        sock_srv = SocketRegistryServer(srv)
        t = SocketTransport(sock_srv.address)
        addr = sock_srv.address
        sock_srv.stop()
        with pytest.raises(DeliveryError):
            t.get_index("app", "v0")
        t.close()

    def test_serve_registry_async_convenience(self):
        reg = Registry(cdmt_params=P)
        pub = ImageClient(LocalTransport(reg), cdc_params=PARAMS,
                          cdmt_params=P)
        pub.commit("app", "v0", _rand(50_000, seed=80))
        pub.push("app", "v0")
        asrv = serve_registry_async(reg)
        try:
            t = MuxSocketTransport(asrv.address)
            cl = ImageClient(t, cdc_params=PARAMS, cdmt_params=P)
            cl.pull("app", "v0")
            assert cl.materialize("app", "v0") is not None
            t.close()
        finally:
            asrv.stop()
