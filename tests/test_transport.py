"""Transport conformance: one ``ImageClient``, six ``Transport``s.

The same scenario must move the same chunks through every transport, with
byte counts equal up to framing overhead — and for the socket and mux
transports, equal to the wire transport's bytes **plus exactly the
envelope overhead** (plain or multiplexed respectively);
swarm pulls must survive provider death mid-pull (failover to the next
source, then the registry); a replicated pull must fan chunk reads across
journal-shipped standbys (and survive primary death by promotion — see
``tests/test_replication.py`` for the replication protocol itself); and the
server's restart warm-up must serve a recovered registry's first wave from
RAM.
"""

import threading
import time

import pytest

from repro.core import cdc, hashing
from repro.core.cdmt import CDMT, CDMTParams
from repro.core.errors import DeliveryError
from repro.core.registry import Registry
from repro.core.store import Recipe
from repro.delivery import (AsyncRegistryServer, FetchResult, ImageClient,
                            JournalFollower, LocalTransport,
                            MuxSocketTransport, PullPlan, RegistryServer,
                            ReplicatedTransport, SocketRegistryServer,
                            SocketTransport, SourceLeg, SwarmNode,
                            SwarmTracker, SwarmTransport, TransferReport,
                            WireTransport, swarm_pull, wire)
from repro.obs import Tracer

PARAMS = cdc.CDCParams(mask_bits=10, min_size=128, max_size=8192)
P = CDMTParams(window=4, rule_bits=2)
TRANSPORTS = ["local", "wire", "socket", "mux", "swarm", "replicated"]


def _rand(n, seed=0):
    import numpy as np
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.uint8).tobytes()


def _versions(n_versions=5, size=150_000, seed=0):
    import numpy as np
    rng = np.random.default_rng(seed)
    data = bytearray(_rand(size, seed))
    out = [bytes(data)]
    for _ in range(n_versions - 1):
        for _ in range(3):
            pos = rng.integers(0, len(data) - 100)
            data[pos:pos + 64] = rng.bytes(64)
        ins = rng.integers(0, len(data))
        data[ins:ins] = rng.bytes(rng.integers(1, 256))
        out.append(bytes(data))
    return out


def _seed_registry(versions, lineage="app"):
    reg = Registry(cdmt_params=P)
    pub = ImageClient(LocalTransport(reg), cdc_params=PARAMS, cdmt_params=P)
    for i, v in enumerate(versions):
        pub.commit(lineage, f"v{i}", v)
        pub.push(lineage, f"v{i}")
    return reg


def _replicated_env(reg, n_standbys=2):
    """Primary + synced standbys, each behind its own socket server, plus a
    ``ReplicatedTransport`` over all of them (primary first).  Returns
    ``(transport, cleanup_objects)``."""
    servers = [SocketRegistryServer(RegistryServer(reg))]
    primary_wire = WireTransport(servers[0].server)
    for i in range(n_standbys):
        sreg = Registry(cdmt_params=P)
        # catch_up, not sync_once: the first standby's ack trims the
        # primary's log, so later standbys join via snapshot bootstrap
        JournalFollower(sreg, primary_wire, name=f"s{i}").catch_up()
        servers.append(SocketRegistryServer(RegistryServer(sreg)))
    transports = [SocketTransport(s.address) for s in servers]
    return ReplicatedTransport(transports), transports + servers


def _fresh_client(kind, reg, provisioned_tags=()):
    """A cold ImageClient over transport ``kind``.  For swarm, one peer is
    pre-provisioned per tag in ``provisioned_tags`` so providers exist.
    Socket/replicated clients carry their servers on ``_cleanup`` — call
    ``_cleanup_client`` when done."""
    if kind == "local":
        return ImageClient(LocalTransport(reg), cdc_params=PARAMS,
                           cdmt_params=P)
    if kind == "replicated":
        transport, cleanup = _replicated_env(reg)
        cl = ImageClient(transport, cdc_params=PARAMS, cdmt_params=P)
        cl._cleanup = cleanup
        return cl
    srv = RegistryServer(reg)
    if kind == "wire":
        return ImageClient(WireTransport(srv), cdc_params=PARAMS,
                           cdmt_params=P)
    if kind == "socket":
        sock_srv = SocketRegistryServer(srv)
        transport = SocketTransport(sock_srv.address)
        cl = ImageClient(transport, cdc_params=PARAMS, cdmt_params=P)
        cl._cleanup = (transport, sock_srv)
        return cl
    if kind == "mux":
        asrv = AsyncRegistryServer(srv)
        transport = MuxSocketTransport(asrv.address)
        cl = ImageClient(transport, cdc_params=PARAMS, cdmt_params=P)
        cl._cleanup = (transport, asrv)
        return cl
    tracker = SwarmTracker()
    for i, tag in enumerate(provisioned_tags):
        peer = SwarmNode(f"seed{i}", cdc_params=PARAMS, cdmt_params=P)
        swarm_pull(peer, srv, tracker, "app", tag)
    node = SwarmNode("me", cdc_params=PARAMS, cdmt_params=P)
    transport = SwarmTransport(node, tracker, srv)
    return ImageClient(transport, store=node.client.store,
                       indexes=node.client.indexes,
                       tag_trees=node.client.tag_trees,
                       cdc_params=PARAMS, cdmt_params=P)


def _close_all(objs):
    for obj in objs:                          # transports first, then servers
        for meth in ("close", "stop"):
            fn = getattr(obj, meth, None)
            if fn is not None:
                fn()


def _cleanup_client(cl):
    _close_all(getattr(cl, "_cleanup", ()))


# ------------------------------------------------------------- conformance

class TestConformance:
    @pytest.fixture(scope="class")
    def scenario(self):
        """Cold pull of v0 then warm upgrade to the head, once per
        transport, against identically-seeded registries."""
        versions = _versions(6, seed=40)
        head = f"v{len(versions) - 1}"
        out = {}
        for kind in TRANSPORTS:
            reg = _seed_registry(versions)
            cl = _fresh_client(kind, reg, provisioned_tags=("v0", head))
            try:
                cold = cl.pull("app", "v0")
                warm = cl.pull("app", head)
                out[kind] = {
                    "cold": cold, "warm": warm,
                    "v0": cl.materialize("app", "v0"),
                    "head": cl.materialize("app", head),
                }
            finally:
                _cleanup_client(cl)
        return versions, out

    def test_materialization_identical(self, scenario):
        versions, out = scenario
        for kind in TRANSPORTS:
            assert out[kind]["v0"] == versions[0], kind
            assert out[kind]["head"] == versions[-1], kind

    def test_identical_chunks_moved(self, scenario):
        _, out = scenario
        for phase in ("cold", "warm"):
            moved = {k: out[k][phase].chunks_moved for k in TRANSPORTS}
            assert len(set(moved.values())) == 1, moved
            totals = {k: out[k][phase].chunks_total for k in TRANSPORTS}
            assert len(set(totals.values())) == 1, totals
            comps = {k: out[k][phase].comparisons for k in TRANSPORTS}
            assert len(set(comps.values())) == 1, comps

    def test_index_and_recipe_bytes_exact_local_vs_wire(self, scenario):
        """The local transport's arithmetic sizing must equal the wire
        transport's real frame lengths byte-for-byte."""
        _, out = scenario
        for phase in ("cold", "warm"):
            a, b = out["local"][phase], out["wire"][phase]
            assert a.index_bytes == b.index_bytes
            assert a.recipe_bytes == b.recipe_bytes
            assert a.chunk_bytes == b.chunk_bytes

    def test_chunk_bytes_within_framing_overhead(self, scenario):
        _, out = scenario
        for phase in ("cold", "warm"):
            ref = out["local"][phase].chunk_bytes
            for kind in TRANSPORTS:
                got = out[kind][phase].chunk_bytes
                assert abs(got - ref) <= 0.02 * ref + 512, (kind, phase)

    def test_reports_carry_transport_and_sources(self, scenario):
        _, out = scenario
        for kind in TRANSPORTS:
            rep = out[kind]["warm"]
            assert isinstance(rep, TransferReport)
            assert rep.transport == kind
            assert sum(l.chunks for l in rep.sources.values()) \
                == rep.chunks_moved
            assert sum(l.chunk_bytes for l in rep.sources.values()) \
                == rep.chunk_bytes

    def test_swarm_pulled_mostly_from_peers(self, scenario):
        _, out = scenario
        warm = out["swarm"]["warm"]
        assert warm.chunks_from_peers >= 0.5 * warm.chunks_moved
        assert warm.peer_offload_fraction >= 0.5


class TestSocketConformance:
    """The socket transport's acceptance gate: same chunks as local/wire
    over real TCP, bytes equal to the wire path plus exactly the envelope
    overhead, plans quoted to the byte, and a mid-pull server death that
    commits nothing."""

    def _socket_client(self, reg, **transport_kw):
        srv = RegistryServer(reg)
        sock_srv = SocketRegistryServer(srv)
        transport = SocketTransport(sock_srv.address, **transport_kw)
        cl = ImageClient(transport, cdc_params=PARAMS, cdmt_params=P)
        cl._cleanup = (transport, sock_srv)
        return cl, srv, sock_srv

    def test_socket_bytes_are_wire_bytes_plus_envelope(self):
        versions = _versions(4, seed=58)
        wire_cl = _fresh_client("wire", _seed_registry(versions))
        sock_cl = _fresh_client("socket", _seed_registry(versions))
        try:
            wplan = wire_cl.plan_pull("app", "v0")
            wrep = wire_cl.execute(wplan)
            splan = sock_cl.plan_pull("app", "v0")
            srep = sock_cl.execute(splan)
            assert splan.missing == wplan.missing

            # chunk traffic: same CHUNK_BATCH frames, plus one response
            # envelope per WANT round
            size_of = dict(zip(splan.recipe.fps, splan.recipe.sizes))
            sizes = [size_of[fp] for fp in splan.missing]
            sub = sock_cl.transport.response_batch_chunks
            envelope = 0
            for start in range(0, len(sizes), sock_cl.batch_chunks):
                lens = wire.chunk_batch_frame_lens(
                    sizes[start:start + sock_cl.batch_chunks], sub)
                envelope += wire.response_envelope_bytes(lens) - sum(lens)
            assert srep.chunk_bytes == wrep.chunk_bytes + envelope

            # control traffic: the same INDEX/RECIPE frame, plus request
            # envelope (new on socket) and response envelope
            for sock_b, frame_len in ((srep.index_bytes, wrep.index_bytes),
                                      (srep.recipe_bytes, wrep.recipe_bytes)):
                assert sock_b == (
                    wire.request_envelope_bytes("app", "v0", [])
                    + wire.response_envelope_bytes([frame_len]))
        finally:
            _cleanup_client(wire_cl)
            _cleanup_client(sock_cl)

    def test_plan_quote_exact_with_server_split_and_envelope(self):
        """Client batches larger than the server's response split stream as
        several frames inside one envelope — the plan quotes all of it."""
        versions = _versions(3, seed=59)
        reg = _seed_registry(versions)
        srv = RegistryServer(reg, max_batch_chunks=16)
        sock_srv = SocketRegistryServer(srv)
        transport = SocketTransport(sock_srv.address, batch_chunks=256)
        try:
            cl = ImageClient(transport, cdc_params=PARAMS, cdmt_params=P,
                             batch_chunks=256)
            assert transport.response_batch_chunks == 16   # INFO handshake
            plan = cl.plan_pull("app", "v2")
            assert plan.chunks_to_fetch > 16               # forces a split
            report = cl.execute(plan)
            assert (report.index_bytes + report.recipe_bytes
                    + report.chunk_bytes) == plan.expected_wire_bytes
        finally:
            transport.close()
            sock_srv.stop()

    def test_mid_pull_server_death_commits_nothing(self):
        """The server dies after streaming one CHUNK_BATCH of a multi-frame
        response: the client must surface DeliveryError (not hang, not a
        bare socket error) with nothing committed to the local store."""
        versions = _versions(3, seed=60)
        reg = _seed_registry(versions)
        srv = RegistryServer(reg, max_batch_chunks=8)
        sock_srv = SocketRegistryServer(srv)
        transport = SocketTransport(sock_srv.address, batch_chunks=1024)
        try:
            cl = ImageClient(transport, cdc_params=PARAMS, cdmt_params=P,
                             batch_chunks=1024)
            plan = cl.plan_pull("app", "v0")
            assert plan.chunks_to_fetch > 8    # multi-frame response

            real_want_plan = srv.want_plan

            def dying_want_plan(want_frame):
                n, frames = real_want_plan(want_frame)

                def die_after_first():
                    yield next(iter(frames))
                    raise RuntimeError("registry crashed mid-stream")

                return n, die_after_first()

            srv.want_plan = dying_want_plan
            chunks_before = cl.store.chunks.n_chunks()
            with pytest.raises(DeliveryError):
                cl.execute(plan)
            assert "app:v0" not in cl.store.recipes
            assert cl.store.chunks.n_chunks() == chunks_before
            assert "app" not in cl.indexes
        finally:
            transport.close()
            sock_srv.stop()

    def test_mux_bytes_are_wire_bytes_plus_mux_envelope(self):
        """The multiplexed transport's byte accounting must relate to the
        frame-level wire transport exactly like the plain socket's does —
        same frames, plus exactly the mux envelope (HEADER + per-frame
        FRAME messages, fixed-width stream ids)."""
        versions = _versions(4, seed=58)
        wire_cl = _fresh_client("wire", _seed_registry(versions))
        mux_cl = _fresh_client("mux", _seed_registry(versions))
        try:
            wplan = wire_cl.plan_pull("app", "v0")
            wrep = wire_cl.execute(wplan)
            mplan = mux_cl.plan_pull("app", "v0")
            mrep = mux_cl.execute(mplan)
            assert mplan.missing == wplan.missing

            size_of = dict(zip(mplan.recipe.fps, mplan.recipe.sizes))
            sizes = [size_of[fp] for fp in mplan.missing]
            sub = mux_cl.transport.response_batch_chunks
            envelope = 0
            for start in range(0, len(sizes), mux_cl.batch_chunks):
                lens = wire.chunk_batch_frame_lens(
                    sizes[start:start + mux_cl.batch_chunks], sub)
                envelope += wire.mux_response_envelope_bytes(lens) - sum(lens)
            assert mrep.chunk_bytes == wrep.chunk_bytes + envelope

            for mux_b, frame_len in ((mrep.index_bytes, wrep.index_bytes),
                                     (mrep.recipe_bytes, wrep.recipe_bytes)):
                assert mux_b == (
                    wire.mux_request_envelope_bytes("app", "v0", [])
                    + wire.mux_response_envelope_bytes([frame_len]))
        finally:
            _cleanup_client(wire_cl)
            _cleanup_client(mux_cl)

    def test_mux_plan_quote_exact_with_server_split(self):
        versions = _versions(3, seed=59)
        reg = _seed_registry(versions)
        srv = RegistryServer(reg, max_batch_chunks=16)
        asrv = AsyncRegistryServer(srv)
        transport = MuxSocketTransport(asrv.address, batch_chunks=256)
        try:
            cl = ImageClient(transport, cdc_params=PARAMS, cdmt_params=P,
                             batch_chunks=256)
            assert transport.response_batch_chunks == 16   # INFO handshake
            plan = cl.plan_pull("app", "v2")
            assert plan.chunks_to_fetch > 16               # forces a split
            report = cl.execute(plan)
            assert (report.index_bytes + report.recipe_bytes
                    + report.chunk_bytes) == plan.expected_wire_bytes
        finally:
            transport.close()
            asrv.stop()

    def test_mux_mid_pull_server_death_commits_nothing(self):
        """A handler crash after the stream header committed its frame
        count kills the connection; the client must surface DeliveryError
        with nothing committed — identical to the threaded contract."""
        versions = _versions(3, seed=60)
        reg = _seed_registry(versions)
        srv = RegistryServer(reg, max_batch_chunks=8)
        asrv = AsyncRegistryServer(srv)
        transport = MuxSocketTransport(asrv.address, batch_chunks=1024)
        try:
            cl = ImageClient(transport, cdc_params=PARAMS, cdmt_params=P,
                             batch_chunks=1024)
            plan = cl.plan_pull("app", "v0")
            assert plan.chunks_to_fetch > 8    # multi-frame response

            real_want_plan = srv.want_plan

            def dying_want_plan(want_frame):
                n, frames = real_want_plan(want_frame)

                def die_after_first():
                    yield next(iter(frames))
                    raise RuntimeError("registry crashed mid-stream")

                return n, die_after_first()

            srv.want_plan = dying_want_plan
            chunks_before = cl.store.chunks.n_chunks()
            with pytest.raises(DeliveryError):
                cl.execute(plan)
            assert "app:v0" not in cl.store.recipes
            assert cl.store.chunks.n_chunks() == chunks_before
            assert "app" not in cl.indexes
        finally:
            transport.close()
            asrv.stop()

    def test_swarm_over_socket_registry_fallback(self):
        """SwarmTransport composes peers over *any* registry transport —
        here the fallback crosses a real socket."""
        versions = _versions(3, seed=61)
        reg = _seed_registry(versions)
        srv = RegistryServer(reg)
        sock_srv = SocketRegistryServer(srv)
        fallback = SocketTransport(sock_srv.address)
        try:
            tracker = SwarmTracker()
            node = SwarmNode("s0", cdc_params=PARAMS, cdmt_params=P)
            transport = SwarmTransport(node, tracker, fallback)
            cl = ImageClient(transport, store=node.client.store,
                             indexes=node.client.indexes,
                             tag_trees=node.client.tag_trees,
                             cdc_params=PARAMS, cdmt_params=P)
            rep = cl.pull("app", "v2")
            assert cl.materialize("app", "v2") == versions[2]
            assert rep.transport == "swarm"
            assert rep.registry_chunk_bytes > 0    # fallback carried it
            # the next swarm puller rides the first as a peer, fetching
            # only the remainder over the socket
            node2 = SwarmNode("s1", cdc_params=PARAMS, cdmt_params=P)
            t2 = SwarmTransport(node2, tracker, fallback)
            cl2 = ImageClient(t2, store=node2.client.store,
                              indexes=node2.client.indexes,
                              tag_trees=node2.client.tag_trees,
                              cdc_params=PARAMS, cdmt_params=P)
            rep2 = cl2.pull("app", "v2")
            assert cl2.materialize("app", "v2") == versions[2]
            assert rep2.chunks_from_peers > 0
        finally:
            fallback.close()
            sock_srv.stop()


# ------------------------------------------- snapshot-bootstrapped standby

class TestBootstrappedStandby:
    """A standby that joined via snapshot bootstrap (the primary's log was
    trimmed, so no offset-0 history existed to replay) must be
    indistinguishable from a history-replayed one: byte-identical pulls on
    every remote transport, and exact plan quotes through the replicated
    transport."""

    def _bootstrapped_standby(self, versions):
        reg = _seed_registry(versions)
        srv = RegistryServer(reg)
        t = WireTransport(srv)
        # every record acked -> the log trims to its head; the fresh
        # standby below cannot replay history and must bootstrap
        t.ack_journal("acked", reg.replication.epoch, reg.replication.head())
        assert reg.replication.base == reg.replication.head()
        sreg = Registry(cdmt_params=P)
        JournalFollower(sreg, t, name="s0").catch_up()
        assert srv.snapshot().snapshot_requests == 1
        return reg, sreg

    @pytest.mark.parametrize("kind", ["wire", "socket", "mux"])
    def test_serves_byte_identical_pulls(self, kind):
        versions = _versions(4, seed=67)
        reg, sreg = self._bootstrapped_standby(versions)
        head = f"v{len(versions) - 1}"
        ref_cl = _fresh_client(kind, reg)
        cl = _fresh_client(kind, sreg)
        try:
            for tag, data in (("v0", versions[0]), (head, versions[-1])):
                want = ref_cl.pull("app", tag)
                got = cl.pull("app", tag)
                assert cl.materialize("app", tag) == data
                assert got.index_bytes == want.index_bytes
                assert got.recipe_bytes == want.recipe_bytes
                assert got.chunk_bytes == want.chunk_bytes
                assert got.chunks_moved == want.chunks_moved
        finally:
            _cleanup_client(ref_cl)
            _cleanup_client(cl)

    def test_replicated_plan_quote_exact_with_bootstrapped_replica(self):
        """``_replicated_env``'s second standby joins via bootstrap (the
        first standby's ack trimmed the log) — the replicated plan must
        still quote socket bytes to the byte, and the bootstrapped standby
        must pass the freshness probe like any other replica."""
        versions = _versions(3, seed=68)
        reg = _seed_registry(versions)
        rt, cleanup = _replicated_env(reg)
        try:
            assert reg.replication.base > 0     # the log really was trimmed
            cl = ImageClient(rt, cdc_params=PARAMS, cdmt_params=P)
            plan = cl.plan_pull("app", "v2")
            report = cl.execute(plan)
            assert (report.index_bytes + report.recipe_bytes
                    + report.chunk_bytes) == plan.expected_wire_bytes
            assert cl.materialize("app", "v2") == versions[2]
            assert rt.stale_detected == 0   # bootstrapped standby is fresh
        finally:
            _close_all(cleanup)

    def test_quote_chunk_batches_routes_per_replica(self):
        versions = _versions(2, seed=69)
        reg = _seed_registry(versions)
        rt, cleanup = _replicated_env(reg)
        try:
            sizes = [500, 9_000, 3, 70_000]
            assert rt.quote_chunk_batches(sizes) \
                == rt.primary_transport.quote_chunk_batches(sizes)
            for i, t in enumerate(rt.replicas):
                assert rt.quote_chunk_batches(sizes, replica=i) \
                    == t.quote_chunk_batches(sizes)
            with pytest.raises(ValueError):
                rt.quote_chunk_batches(sizes, replica=len(rt.replicas))
        finally:
            _close_all(cleanup)


class TestPushConformance:
    @pytest.mark.parametrize("kind", TRANSPORTS)
    def test_push_lands_identically(self, kind):
        versions = _versions(3, seed=41)
        reg = Registry(cdmt_params=P)
        cleanup = []
        if kind == "local":
            transport = LocalTransport(reg)
        elif kind == "wire":
            transport = WireTransport(RegistryServer(reg))
        elif kind == "socket":
            sock_srv = SocketRegistryServer(RegistryServer(reg))
            transport = SocketTransport(sock_srv.address)
            cleanup = [transport, sock_srv]
        elif kind == "replicated":
            # pushes route to the primary; standbys never see them directly
            transport, cleanup = _replicated_env(reg)
        else:
            node = SwarmNode("pub", cdc_params=PARAMS, cdmt_params=P)
            transport = SwarmTransport(node, SwarmTracker(),
                                       RegistryServer(reg))
        try:
            pub = ImageClient(transport, cdc_params=PARAMS, cdmt_params=P)
            reference = _seed_registry(versions)
            for i, v in enumerate(versions):
                pub.commit("app", f"v{i}", v)
                st = pub.push("app", f"v{i}")
                assert st.chunks_moved <= st.chunks_total
            assert reg.tags("app") == reference.tags("app")
            for tag in reg.tags("app"):
                assert reg.index_for_tag("app", tag).root \
                    == reference.index_for_tag("app", tag).root
        finally:
            _close_all(cleanup)

    @pytest.mark.parametrize("kind", ["local", "wire"])
    def test_has_chunks_gives_cross_lineage_push_dedup(self, kind):
        """A push ships only chunks the backend truly lacks — shared chunks
        already stored under another lineage stay home."""
        base = _rand(100_000, seed=42)
        reg = Registry(cdmt_params=P)
        transport = (LocalTransport(reg) if kind == "local"
                     else WireTransport(RegistryServer(reg)))
        pub = ImageClient(transport, cdc_params=PARAMS, cdmt_params=P)
        pub.commit("a", "v0", base)
        pub.push("a", "v0")
        pub.commit("b", "v0", base + _rand(10_000, seed=43))
        st = pub.push("b", "v0")
        # lineage b is new (no index to diff against) yet most chunks are
        # already stored under lineage a — the presence check finds them
        assert st.chunks_moved < 0.5 * st.chunks_total
        assert st.want_bytes >= 0
        fresh = ImageClient(transport, cdc_params=PARAMS, cdmt_params=P)
        fresh.pull("b", "v0")
        assert fresh.materialize("b", "v0") == pub.materialize("b", "v0")


# ------------------------------------------------------------ plan/execute

class TestPlanExecute:
    def test_plan_is_inspectable_and_execute_matches(self):
        versions = _versions(4, seed=44)
        reg = _seed_registry(versions)
        cl = _fresh_client("wire", reg)
        cl.pull("app", "v0")
        plan = cl.plan_pull("app", "v3")
        assert isinstance(plan, PullPlan)
        assert 0 < plan.chunks_to_fetch < plan.chunks_total
        assert plan.comparisons > 0
        assert plan.expected_chunk_bytes < plan.raw_bytes
        # nothing moved yet: planning is free of data-plane traffic
        assert "app:v3" not in cl.store.recipes
        report = cl.execute(plan)
        assert report.chunks_moved == plan.chunks_to_fetch
        assert report.comparisons == plan.comparisons
        # the plan's quote is exact (want/control frames excluded by design)
        assert (report.index_bytes + report.recipe_bytes
                + report.chunk_bytes) == plan.expected_wire_bytes
        assert cl.materialize("app", "v3") == versions[3]

    def test_plan_quote_exact_for_local_too(self):
        versions = _versions(3, seed=45)
        reg = _seed_registry(versions)
        cl = _fresh_client("local", reg)
        plan = cl.plan_pull("app", "v0")
        report = cl.execute(plan)
        assert (report.index_bytes + report.recipe_bytes
                + report.chunk_bytes) == plan.expected_wire_bytes

    def test_plan_quote_exact_when_server_splits_batches(self):
        """A client request batch larger than the server's response batch
        limit gets split into more frames — the plan must quote that."""
        versions = _versions(3, seed=48)
        reg = _seed_registry(versions)
        srv = RegistryServer(reg, max_batch_chunks=16)
        cl = ImageClient(WireTransport(srv), cdc_params=PARAMS,
                         cdmt_params=P, batch_chunks=256)
        plan = cl.plan_pull("app", "v2")
        assert plan.chunks_to_fetch > 16          # forces a server split
        report = cl.execute(plan)
        assert (report.index_bytes + report.recipe_bytes
                + report.chunk_bytes) == plan.expected_wire_bytes

    def test_plan_wrong_transport_rejected(self):
        versions = _versions(2, seed=46)
        reg = _seed_registry(versions)
        plan = _fresh_client("local", reg).plan_pull("app", "v0")
        with pytest.raises(DeliveryError):
            _fresh_client("wire", reg).execute(plan)

    def test_upgrade_pulls_head(self):
        versions = _versions(4, seed=47)
        reg = _seed_registry(versions)
        cl = _fresh_client("wire", reg)
        rep = cl.upgrade("app")
        assert rep.tag == "v3"
        assert cl.materialize("app", "v3") == versions[3]
        with pytest.raises(DeliveryError):
            cl.upgrade("ghost")


# ---------------------------------------------------------------- failover

class TestFailover:
    def _swarm_env(self, n_versions=4, seed=50):
        versions = _versions(n_versions, seed=seed)
        reg = _seed_registry(versions)
        srv = RegistryServer(reg)
        tracker = SwarmTracker()
        head = f"v{n_versions - 1}"
        peer = SwarmNode("p0", cdc_params=PARAMS, cdmt_params=P)
        swarm_pull(peer, srv, tracker, "app", head)
        return versions, srv, tracker, peer, head

    def test_dead_peer_falls_over_to_registry(self):
        versions, srv, tracker, peer, head = self._swarm_env()
        peer.kill()
        node = SwarmNode("n1", cdc_params=PARAMS, cdmt_params=P)
        st = swarm_pull(node, srv, tracker, "app", head, batch_chunks=16)
        assert node.client.materialize("app", head) == versions[-1]
        assert st.failovers >= 1
        assert st.chunks_from_peers == 0
        assert st.peer_offload_fraction == 0.0
        assert st.registry_chunk_bytes > 0
        leg = st.sources[f"peer:{peer.name}"]
        assert leg.failures >= 1 and leg.chunks == 0

    def test_peer_dies_mid_pull(self):
        """The provider answers the first batch, then goes dark — the pull
        must complete against the registry with the death recorded as a
        failover, not fail or hang."""
        versions, srv, tracker, peer, head = self._swarm_env()
        real_serve = peer.serve_want
        served = []

        def dying_serve(want_frame):
            if served:
                peer.kill()
            served.append(1)
            return real_serve(want_frame)

        peer.serve_want = dying_serve
        node = SwarmNode("n1", cdc_params=PARAMS, cdmt_params=P)
        st = swarm_pull(node, srv, tracker, "app", head, batch_chunks=8)
        assert node.client.materialize("app", head) == versions[-1]
        assert st.chunks_from_peers > 0          # first batch came from it
        assert st.failovers >= 1                 # later batches hit the corpse
        assert st.registry_chunk_bytes > 0       # registry served the rest
        assert st.chunks_moved == st.chunks_total

    def test_dead_provider_benched_after_threshold_then_revived(self):
        """Tracker health (churn): a provider that keeps failing is benched
        after ``failure_threshold`` consecutive failures — later batches and
        later pullers stop paying one failed round each — and ``revive()``
        re-registers it on every tracker it joined."""
        versions, srv, tracker, peer, head = self._swarm_env()
        peer.kill()
        node = SwarmNode("n1", cdc_params=PARAMS, cdmt_params=P)
        st = swarm_pull(node, srv, tracker, "app", head, batch_chunks=8)
        assert node.client.materialize("app", head) == versions[-1]
        # enough batches ran to exceed the threshold many times over, but
        # the corpse only cost threshold failed rounds before the bench
        assert st.rounds > tracker.failure_threshold
        assert st.failovers == tracker.failure_threshold
        assert tracker.is_benched(peer)
        # a benched provider is invisible to the next puller
        node2 = SwarmNode("n2", cdc_params=PARAMS, cdmt_params=P)
        st2 = swarm_pull(node2, srv, tracker, "app", head, batch_chunks=8)
        assert st2.failovers == 0
        assert f"peer:{peer.name}" not in st2.sources
        # revive: back online, backoff cleared, serving again
        peer.revive()
        assert not tracker.is_benched(peer)
        node3 = SwarmNode("n3", cdc_params=PARAMS, cdmt_params=P)
        st3 = swarm_pull(node3, srv, tracker, "app", head, batch_chunks=8)
        assert st3.failovers == 0
        assert st3.chunks_from_peers > 0

    def test_success_resets_failure_streak(self):
        """Failures must be *consecutive* to bench: a flaky peer that
        recovers before the threshold keeps serving."""
        versions, srv, tracker, peer, head = self._swarm_env()
        for _ in range(tracker.failure_threshold - 1):
            tracker.report_failure(peer)
        assert not tracker.is_benched(peer)
        tracker.report_success(peer)
        assert tracker.consecutive_failures(peer) == 0
        for _ in range(tracker.failure_threshold - 1):
            tracker.report_failure(peer)
        assert not tracker.is_benched(peer)
        assert peer in tracker.providers("app", head)

    def test_live_provider_preferred_over_dead(self):
        """The tracker orders live nodes ahead of dead ones in each tier, so
        a lingering corpse neither crowds out the live provider nor costs a
        failed round when the live one can serve everything."""
        versions, srv, tracker, peer, head = self._swarm_env()
        backup = SwarmNode("p1", cdc_params=PARAMS, cdmt_params=P)
        swarm_pull(backup, srv, tracker, "app", head)
        peer.kill()
        node = SwarmNode("n2", cdc_params=PARAMS, cdmt_params=P)
        st = swarm_pull(node, srv, tracker, "app", head, batch_chunks=16)
        assert node.client.materialize("app", head) == versions[-1]
        # the live provider served the bytes; the corpse was never consulted
        assert st.failovers == 0
        assert st.chunks_from_peers == st.chunks_moved
        assert st.sources[f"peer:{backup.name}"].chunks > 0
        assert f"peer:{peer.name}" not in st.sources


# ---------------------------------------------------------- pipeline bound


class _CountingTransport:
    """Fake transport serving canned chunks, counting fetch rounds."""

    name = "fake"
    verifies_payloads = True

    def __init__(self, chunks):
        self.chunks = dict(chunks)
        self.calls = 0
        self._lock = threading.Lock()

    def fetch_chunks(self, lineage, tag, fps):
        with self._lock:
            self.calls += 1
        time.sleep(0.002)                   # give the pipeline time to race
        got = {fp: self.chunks[fp] for fp in fps if fp in self.chunks}
        leg = SourceLeg(source="registry", chunks=len(got),
                        chunk_bytes=sum(len(v) for v in got.values()),
                        rounds=1)
        return FetchResult(chunks=got, legs=[leg])

    def notify_pulled(self, lineage, tag):
        pass


class TestPipelineBound:
    def _plan(self, n_chunks=24):
        payloads = [bytes([i]) * (50 + i) for i in range(n_chunks)]
        fps = [hashing.chunk_fingerprint(d) for d in payloads]
        recipe = Recipe(name="app:v0", fps=fps,
                        sizes=[len(d) for d in payloads])
        plan = PullPlan(lineage="app", tag="v0", transport="fake",
                        index=CDMT.build(fps, params=P), recipe=recipe,
                        missing=list(fps), chunks_total=len(fps),
                        raw_bytes=sum(recipe.sizes))
        return plan, dict(zip(fps, payloads)), b"".join(payloads)

    def test_at_most_pipeline_depth_batches_in_flight(self, monkeypatch):
        """The documented bound is ``pipeline_depth`` batches in flight;
        the old loop drained only *after* submitting, keeping depth+1."""
        from repro.delivery import client as client_mod
        outstanding = {"now": 0, "max": 0}
        lock = threading.Lock()
        real_executor = client_mod.ThreadPoolExecutor

        class ProbeFuture:
            def __init__(self, fut):
                self._fut = fut

            def result(self):
                out = self._fut.result()
                with lock:
                    outstanding["now"] -= 1
                return out

        class ProbeExecutor(real_executor):
            def submit(self, fn, *args, **kw):
                with lock:
                    outstanding["now"] += 1
                    outstanding["max"] = max(outstanding["max"],
                                             outstanding["now"])
                return ProbeFuture(super().submit(fn, *args, **kw))

        monkeypatch.setattr(client_mod, "ThreadPoolExecutor", ProbeExecutor)
        plan, chunks, raw = self._plan()
        transport = _CountingTransport(chunks)
        cl = ImageClient(transport, cdc_params=PARAMS, cdmt_params=P,
                         batch_chunks=2, pipeline_depth=3)
        report = cl.execute(plan)
        assert transport.calls == 12            # 24 chunks / batches of 2
        assert report.chunks_moved == 24
        assert outstanding["max"] == 3          # == depth, never depth + 1
        assert cl.materialize("app", "v0") == raw


# ----------------------------------------------------------- push integrity


class TestPushLocalStore:
    def test_missing_local_candidate_is_delivery_error(self):
        """A candidate fp the local store cannot produce must fail as a
        protocol-level DeliveryError naming the fp, not a bare KeyError."""
        reg = Registry(cdmt_params=P)
        cl = ImageClient(LocalTransport(reg), cdc_params=PARAMS,
                         cdmt_params=P)
        recipe = cl.commit("app", "v0", _rand(60_000, seed=62))
        victim = recipe.fps[0]
        real_get = cl.store.chunks.get

        def missing_get(fp):
            if fp == victim:
                raise KeyError(fp)
            return real_get(fp)

        cl.store.chunks.get = missing_get
        with pytest.raises(DeliveryError) as ei:
            cl.push("app", "v0")
        assert victim.hex()[:12] in str(ei.value)


# ------------------------------------------------------- tag-listing frames


class TestTagsFrames:
    def test_wire_tags_are_metered_protocol_data(self):
        """Tag queries flow through TAGS/TAG_LIST frames and the server's
        meters — not an attribute reach into the registry."""
        versions = _versions(2, seed=63)
        reg = _seed_registry(versions)
        srv = RegistryServer(reg)
        t = WireTransport(srv)
        s0 = srv.snapshot()
        assert t.tags("app") == ["v0", "v1"]
        s1 = srv.snapshot()
        assert s1.tags_requests == s0.tags_requests + 1
        assert s1.ingress_bytes > s0.ingress_bytes
        assert s1.egress_bytes > s0.egress_bytes
        assert t.tags("ghost") == []


# ------------------------------------------------------- HAS/MISSING frames

class TestPresenceFrames:
    def test_roundtrip(self):
        fps = [hashing.chunk_fingerprint(bytes([i])) for i in range(9)]
        assert wire.decode_has(wire.encode_has(fps)) == fps
        assert wire.decode_missing(wire.encode_missing(fps)) == fps
        with pytest.raises(wire.WireError):
            wire.decode_has(wire.encode_missing(fps))   # type mismatch
        with pytest.raises(wire.WireError):
            wire.decode_has(wire.encode_has(fps)[:-1])  # truncation

    def test_server_answers_presence(self):
        versions = _versions(2, seed=51)
        reg = _seed_registry(versions)
        srv = RegistryServer(reg)
        known = next(iter(reg.store.chunks.fingerprints()))
        ghost = hashing.chunk_fingerprint(b"never pushed")
        resp = srv.handle_has(wire.encode_has([known, ghost]))
        assert wire.decode_missing(resp) == [ghost]
        assert srv.snapshot().has_requests == 1


# ----------------------------------------------------------- restart warm-up

class TestWarmStart:
    def _durable_registry(self, tmp_path, versions):
        reg = Registry(directory=str(tmp_path), cdmt_params=P)
        pub = ImageClient(LocalTransport(reg), cdc_params=PARAMS,
                          cdmt_params=P)
        for i, v in enumerate(versions):
            pub.commit("app", f"v{i}", v)
            pub.push("app", f"v{i}")
        reg.close()
        return Registry(directory=str(tmp_path), cdmt_params=P)

    def test_recovered_registry_serves_from_warm_cache(self, tmp_path):
        versions = _versions(3, seed=52)
        reg = self._durable_registry(tmp_path, versions)
        try:
            srv = RegistryServer(reg)
            s0 = srv.snapshot()
            assert s0.warmed_chunks == reg.store.chunks.n_chunks()
            cl = ImageClient(WireTransport(srv), cdc_params=PARAMS,
                             cdmt_params=P)
            cl.pull("app", "v2")
            assert cl.materialize("app", "v2") == versions[2]
            s = srv.snapshot()
            assert s.warm_hits > 0
            # the whole working set was pre-warmed: no cold store reads
            assert srv.cache.stats.misses == 0
        finally:
            reg.close()

    def test_warm_start_opt_out(self, tmp_path):
        versions = _versions(2, seed=53)
        reg = self._durable_registry(tmp_path, versions)
        try:
            srv = RegistryServer(reg, warm_start=False)
            assert srv.snapshot().warmed_chunks == 0
            assert srv.cache.stats.resident_bytes == 0
        finally:
            reg.close()

    def test_warm_start_respects_capacity(self, tmp_path):
        versions = _versions(3, seed=54)
        reg = self._durable_registry(tmp_path, versions)
        try:
            srv = RegistryServer(reg, cache_bytes=20_000)
            s = srv.snapshot()
            assert 0 < s.warmed_chunks < reg.store.chunks.n_chunks()
            assert srv.cache.stats.resident_bytes <= 20_000
        finally:
            reg.close()

    def test_memory_registry_not_warmed(self):
        versions = _versions(2, seed=55)
        reg = _seed_registry(versions)
        srv = RegistryServer(reg)
        assert srv.snapshot().warmed_chunks == 0

    def _chunked_store(self, tmp_path, small_n=20, small_size=1000,
                       big_size=50_000):
        """A durable store whose most recent chunk is far larger than the
        warm budget, with plenty of older small chunks behind it."""
        reg = Registry(directory=str(tmp_path), cdmt_params=P)
        smalls = []
        for i in range(small_n):
            data = _rand(small_size, seed=100 + i)
            reg.store.chunks.put(hashing.chunk_fingerprint(data), data)
            smalls.append(hashing.chunk_fingerprint(data))
        big = _rand(big_size, seed=99)
        big_fp = hashing.chunk_fingerprint(big)
        reg.store.chunks.put(big_fp, big)          # most recently appended
        reg.close()
        return Registry(directory=str(tmp_path), cdmt_params=P), big_fp

    def test_warm_skips_oversized_recent_chunk(self, tmp_path):
        """Regression: one big recent chunk used to stop warming at the
        first reject, leaving the rest of the budget cold even though many
        smaller older chunks still fit."""
        reg, big_fp = self._chunked_store(tmp_path)
        try:
            srv = RegistryServer(reg, cache_bytes=10_000)
            s = srv.snapshot()
            assert s.warmed_chunks >= 9            # ~10 × 1000B fit
            assert big_fp not in srv.cache.resident_fps()
            assert srv.cache.stats.resident_bytes <= 10_000
        finally:
            reg.close()

    def test_warm_scan_limit_bounds_startup(self, tmp_path):
        reg, _big_fp = self._chunked_store(tmp_path)
        try:
            srv = RegistryServer(reg, warm_scan_limit=5)
            # the scan stopped after 5 index entries (big one included)
            assert srv.snapshot().warmed_chunks <= 5
            assert srv.snapshot().warmed_chunks > 0
        finally:
            reg.close()


# --------------------------------------------------- metrics conformance

class TestMetricsConformance:
    """Per-transport metric byte totals must equal the TransferReport for
    the same traffic, byte for byte — the metrics layer is an alternative
    view of the same measurement points, not a second estimate."""

    def _categories(self, transport):
        snap = transport.metrics.snapshot()

        def val(cat):
            return snap.value("transport_bytes_total",
                              {"transport": transport.name, "category": cat})
        return {cat: val(cat) for cat in ("index", "recipe", "want", "chunk")}

    @pytest.mark.parametrize("kind", TRANSPORTS)
    def test_pull_bytes_match_report_exactly(self, kind):
        versions = _versions(4, seed=61)
        reg = _seed_registry(versions)
        cl = _fresh_client(kind, reg, provisioned_tags=("v1",))
        try:
            rep = cl.pull("app", "v2")
            got = self._categories(cl.transport)
            assert got == {"index": rep.index_bytes,
                           "recipe": rep.recipe_bytes,
                           "want": rep.want_bytes,
                           "chunk": rep.chunk_bytes}
            assert sum(got.values()) == rep.total_wire_bytes
        finally:
            _cleanup_client(cl)

    @pytest.mark.parametrize("kind", TRANSPORTS)
    def test_push_bytes_match_report_exactly(self, kind):
        versions = _versions(3, seed=62)
        reg = _seed_registry(versions)
        cl = _fresh_client(kind, reg)
        try:
            data = versions[-1] + _rand(5000, seed=63)
            cl.commit("app", "v9", data)
            rep = cl.push("app", "v9")
            got = self._categories(cl.transport)
            assert got == {"index": rep.index_bytes,
                           "recipe": rep.recipe_bytes,
                           "want": rep.want_bytes,
                           "chunk": rep.chunk_bytes}
        finally:
            _cleanup_client(cl)

    def test_client_adopts_transport_registry(self):
        reg = _seed_registry(_versions(2, seed=64))
        cl = _fresh_client("local", reg)
        assert cl.metrics is cl.transport.metrics
        cl.pull("app", "v1")
        snap = cl.metrics.snapshot()
        h = snap.histogram("client_pull_seconds", {"transport": "local"})
        assert h is not None and h.count == 1


class TestPlanSpans:
    @pytest.mark.parametrize("kind", TRANSPORTS)
    def test_plan_pull_children_carry_the_plan_bytes(self, kind):
        """``plan_pull`` holds its index and recipe fetches as children,
        each with the bytes the plan accounts; the rest is the compare."""
        reg = _seed_registry(_versions(3, seed=66))
        cl = _fresh_client(kind, reg, provisioned_tags=("v2",))
        cl.tracer = Tracer(enabled=True)
        try:
            plan = cl.plan_pull("app", "v2")
        finally:
            _cleanup_client(cl)
        [root] = cl.tracer.take()
        assert root.name == "plan_pull"
        assert [(c.name, c.attrs) for c in root.children] == [
            ("plan_pull.index", {"bytes": plan.index_bytes}),
            ("plan_pull.recipe", {"bytes": plan.recipe_bytes})]
        assert plan.index_bytes > 0 and plan.recipe_bytes > 0
        assert root.duration >= sum(c.duration for c in root.children)


class TestMetricsScrape:
    """``Op.METRICS`` over a live socket: the scraped snapshot must match
    the in-process one (same registry, same numbers)."""

    def test_scrape_matches_in_process_snapshot(self):
        versions = _versions(3, seed=65)
        reg = _seed_registry(versions)
        srv = RegistryServer(reg)
        with SocketRegistryServer(srv) as sock_srv, \
                SocketTransport(sock_srv.address) as transport:
            cl = ImageClient(transport, cdc_params=PARAMS, cdmt_params=P)
            cl.pull("app", "v2")
            scraped = transport.scrape_metrics()
            local = srv.metrics.snapshot()
            # request-latency histogram: same op counts over the wire
            for op in ("index", "recipe", "want"):
                got = scraped.histogram("registry_request_seconds",
                                        {"op": op})
                want = local.histogram("registry_request_seconds",
                                       {"op": op})
                assert got is not None and got.count == want.count
            # counters and cache numbers identical (a scrape adds only
            # "metrics"-op and socket-level series, counted after snapshot)
            assert scraped.value("registry_requests_total", {"op": "want"}) \
                == local.value("registry_requests_total", {"op": "want"})
            assert scraped.value("cache_hits_total", {}) \
                == local.value("cache_hits_total", {})
            assert scraped.value("cache_misses_total", {}) \
                == local.value("cache_misses_total", {})
            # socket envelope series ride in the same scrape
            assert scraped.value("socket_requests_total", {}) >= 1

    def test_scrape_reports_standby_lag(self):
        versions = _versions(3, seed=66)
        reg = _seed_registry(versions)
        srv = RegistryServer(reg)
        with SocketRegistryServer(srv) as sock_srv, \
                SocketTransport(sock_srv.address) as transport:
            standby = Registry(cdmt_params=P)
            try:
                JournalFollower(standby, transport, name="s0").sync_once()
                scraped = transport.scrape_metrics()
                lag = scraped.value("replication_standby_lag",
                                    {"replica": "s0"}, default=None)
                assert lag == 0            # fully caught up and acked
            finally:
                standby.close()
