"""Cross-host fleet federation (PR 15, ``parallel.federation``).

Covers the manifest contract (golden digest + golden probe owners —
the fleet-wide shard-map agreement), the seeded hash ring, device
partitioning, the three new wire ops (manifest_hello / member_gossip /
shard_transfer) against real in-process sidecars, the federated
combined topology (mixed local+remote members, peer byte fetch from
the combined role — the PR 11 follow-on), shard-aware remote
prestage, and THE acceptance drill: a TWO-PROCESS federated fleet
that agrees on golden assignments, survives a member process's death
with shard failover, and completes a cross-host drain with warm wire
handoff and zero 5xx-without-shed.
"""

import asyncio
import json
import os

import numpy as np
import pytest

from omero_ms_image_region_tpu.io.store import build_pyramid
from omero_ms_image_region_tpu.parallel import federation
from omero_ms_image_region_tpu.parallel.federation import (
    FederationCoordinator, FederationError, FleetManifest, MemberSpec,
    partition_local_devices)
from omero_ms_image_region_tpu.parallel.fleet import (
    FleetImageHandler, FleetRouter, HashRing, RemoteMember,
    plane_route_key)
from omero_ms_image_region_tpu.server.config import (
    AppConfig, BatcherConfig, RawCacheConfig, RendererConfig)
from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
from omero_ms_image_region_tpu.server.singleflight import SingleFlight
from omero_ms_image_region_tpu.utils import telemetry

IMG = 1
H = W = 64


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    federation.uninstall()
    federation.reset_gossip()
    yield
    telemetry.reset()
    federation.uninstall()
    federation.reset_gossip()


@pytest.fixture()
def data_dir(tmp_path):
    rng = np.random.default_rng(7)
    planes = rng.integers(0, 60000,
                          size=(2, 1, H, W)).astype(np.uint16)
    build_pyramid(planes, str(tmp_path / str(IMG)), chunk=(32, 32),
                  n_levels=1)
    return str(tmp_path)


def _member_cfg(data_dir):
    return AppConfig(
        data_dir=data_dir,
        batcher=BatcherConfig(enabled=False),
        raw_cache=RawCacheConfig(enabled=True, prefetch=False),
        renderer=RendererConfig(cpu_fallback_max_px=0))


def _manifest(version=1, seed="fed-test"):
    return FleetManifest(
        [MemberSpec("a0", "hostA"), MemberSpec("a1", "hostA"),
         MemberSpec("b0", "hostB", "10.0.0.2:8476"),
         MemberSpec("b1", "hostB", "10.0.0.2:8477")],
        version=version, ring_seed=seed)


def _params(x, y, w=60000, edge=32):
    return {"imageId": str(IMG), "theZ": "0", "theT": "0",
            "tile": f"0,{x},{y},{edge},{edge}", "format": "png",
            "m": "g", "c": f"1|0:{w}$FF0000"}


# ------------------------------------------------------------ manifest

class TestManifest:
    def test_golden_digest_pinned(self):
        """The agreement token is FROZEN: a drifted canonical form
        means two deployed hosts on the same config would read each
        other as split-brain (or worse, silently agree on different
        rings).  Re-pin only with a deliberate epoch-bump migration
        note."""
        m = FleetManifest(
            [MemberSpec("a0", "hostA"), MemberSpec("a1", "hostA"),
             MemberSpec("b0", "hostB", "10.0.0.2:8476"),
             MemberSpec("b1", "hostB", "10.0.0.2:8477")],
            version=3, ring_seed="prod-eu-1", replicas=64)
        assert m.digest() == "6b7cdb655ba71062a37777b0f4ebb2b9"

    def test_golden_probe_owners_pinned(self):
        """The fleet-wide shard map on the agreement probe keys —
        what every joining process verifies against each peer's OWN
        ring math."""
        m = FleetManifest(
            [MemberSpec("a0", "hostA"), MemberSpec("a1", "hostA"),
             MemberSpec("b0", "hostB", "10.0.0.2:8476"),
             MemberSpec("b1", "hostB", "10.0.0.2:8477")],
            version=3, ring_seed="prod-eu-1", replicas=64)
        assert m.owners([f"fed-probe-{i:03d}" for i in range(8)]) == \
            ["b0", "b1", "a1", "a0", "a0", "b1", "a0", "b0"]

    def test_round_trip_preserves_digest(self):
        m = _manifest(version=5)
        again = FleetManifest.from_json(
            json.loads(json.dumps(m.to_json())))
        assert again.digest() == m.digest()
        assert again.version == 5

    def test_validation(self):
        with pytest.raises(ValueError):
            FleetManifest([])
        with pytest.raises(ValueError):
            FleetManifest([MemberSpec("x", "h"), MemberSpec("x", "h")])
        with pytest.raises(ValueError):
            FleetManifest([MemberSpec("x", "h")], version=0)

    def test_local_remote_split(self):
        m = _manifest()
        assert [s.name for s in m.local_members("hostA")] \
            == ["a0", "a1"]
        assert [s.name for s in m.remote_members("hostA")] \
            == ["b0", "b1"]


class TestManifestHello:
    def test_no_manifest_answers_disabled(self):
        assert federation.handle_manifest_hello({}) \
            == {"enabled": False}

    def test_agreement_and_probe_owners(self):
        m = _manifest()
        federation.install(m)
        doc = federation.handle_manifest_hello(
            {"manifest": m.to_json(),
             "probe_keys": ["k1", "k2"]})
        assert doc["agreed"] is True
        assert doc["digest"] == m.digest()
        assert doc["owners"] == m.owners(["k1", "k2"])

    def test_newer_epoch_pends_never_swaps_the_live_manifest(self):
        """A newer epoch from a joiner is recorded PENDING: the ACTIVE
        manifest — the one this process's router was built from and
        actually routes with — never swaps under a live fleet (that
        would silently diverge what we advertise from what we
        route)."""
        federation.install(_manifest(version=1))
        newer = _manifest(version=2)
        doc = federation.handle_manifest_hello(
            {"manifest": newer.to_json()})
        assert doc["agreed"] is False
        assert doc["reason"] == "pending"
        assert doc["pending_version"] == 2
        assert federation.current().version == 1       # unchanged
        assert federation.pending().version == 2

    def test_stale_epoch_answers_ours(self):
        federation.install(_manifest(version=3))
        doc = federation.handle_manifest_hello(
            {"manifest": _manifest(version=1).to_json()})
        assert doc["agreed"] is False
        assert doc["reason"] == "stale-epoch"
        assert doc["manifest"]["version"] == 3

    def test_same_epoch_different_membership_is_split_brain(self):
        federation.install(_manifest(version=2))
        forked = FleetManifest(
            [MemberSpec("a0", "hostA"), MemberSpec("zz", "hostC",
                                                   "c:1")],
            version=2, ring_seed="fed-test")
        doc = federation.handle_manifest_hello(
            {"manifest": forked.to_json()})
        assert doc["agreed"] is False
        assert doc["reason"] == "split-brain"
        # The installed manifest NEVER adopts a same-epoch fork.
        assert federation.current().digest() \
            == _manifest(version=2).digest()


# ----------------------------------------------------------- hash ring

class TestSeededRing:
    def test_empty_seed_is_bit_exact_with_legacy(self):
        """The federation seed must not move a single pre-federation
        key: the PR 8 golden assignments hold for seed ''."""
        a = HashRing(["m0", "m1", "m2", "m3"], replicas=64)
        b = HashRing(["m0", "m1", "m2", "m3"], replicas=64, seed="")
        keys = [f"k{i}" for i in range(500)] + ["plane-000"]
        assert [a.member(k) for k in keys] == \
            [b.member(k) for k in keys]
        assert a.member("plane-000") == "m3"        # the PR 8 pin

    def test_seeded_golden_assignments_pinned(self):
        """A SEEDED ring's map is frozen too — it is part of the
        agreed manifest identity."""
        r = HashRing(["m0", "m1", "m2", "m3"], replicas=64,
                     seed="prod-eu-1")
        assert {k: r.member(k) for k in
                ("plane-000", "plane-001", "plane-002",
                 "plane-003")} == {
            "plane-000": "m0", "plane-001": "m2",
            "plane-002": "m2", "plane-003": "m3"}

    def test_different_seeds_shear_the_key_space(self):
        a = HashRing(["m0", "m1", "m2", "m3"], seed="fed-a")
        b = HashRing(["m0", "m1", "m2", "m3"], seed="fed-b")
        keys = [f"k{i}" for i in range(400)]
        moved = sum(a.member(k) != b.member(k) for k in keys)
        assert moved > 100      # ~3/4 expected; any overlap-heavy
        # result means the seed is not actually folded into the hash

    def test_router_passes_seed_through(self, data_dir):
        from omero_ms_image_region_tpu.parallel.fleet import (
            build_local_members)
        from omero_ms_image_region_tpu.server.app import build_services
        config = _member_cfg(data_dir)
        services = build_services(config)
        try:
            members = build_local_members(config, services, 2)
            router = FleetRouter(members, ring_seed="prod-eu-1")
            assert router.ring.seed == "prod-eu-1"
        finally:
            services.pixels_service.close()


# ------------------------------------------------------ device pinning

class TestDevicePartition:
    def test_even_and_remainder_splits(self):
        assert partition_local_devices(2, ["d0", "d1", "d2", "d3"]) \
            == [["d0", "d1"], ["d2", "d3"]]
        # Remainder lands on the EARLIEST members (member 0 — the
        # mesh/bulk lane — is never the short one).
        assert partition_local_devices(3, list("abcde")) == \
            [["a", "b"], ["c", "d"], ["e"]]

    def test_fewer_devices_than_members_leaves_tail_unpinned(self):
        assert partition_local_devices(3, ["d0"]) == [["d0"], [], []]
        assert partition_local_devices(2, []) == [[], []]

    def test_members_carry_their_device_sets(self, data_dir):
        from omero_ms_image_region_tpu.parallel.fleet import (
            build_local_members)
        from omero_ms_image_region_tpu.server.app import build_services
        config = _member_cfg(data_dir)
        services = build_services(config)
        try:
            members = build_local_members(
                config, services, 2,
                device_sets=[["devA"], ["devB"]])
            assert members[0].devices == ("devA",)
            assert members[1].devices == ("devB",)
            assert services.pin_device == "devA"
            assert members[1].services.pin_device == "devB"
            assert members[1].services.renderer.device == "devB"
        finally:
            services.pixels_service.close()


# ------------------------------------------------------------- wire ops

async def _wait_socket(sock, task):
    for _ in range(400):
        if task.done():
            raise AssertionError(
                f"sidecar died at startup: {task.exception()!r}")
        if os.path.exists(sock):
            return
        await asyncio.sleep(0.05)
    raise AssertionError("sidecar socket never appeared")


class TestWireOps:
    def test_manifest_hello_and_gossip_over_the_wire(self, data_dir,
                                                     tmp_path):
        """A real sidecar process-alike (in-process run_sidecar) with
        an installed manifest answers agreement, probe owners from
        ITS OWN ring math, and gossip merges."""
        from omero_ms_image_region_tpu.server.sidecar import (
            SidecarClient, run_sidecar)

        sock = str(tmp_path / "fed.sock")
        manifest = _manifest()
        federation.install(manifest)

        async def scenario():
            task = asyncio.create_task(
                run_sidecar(_member_cfg(data_dir), sock))
            await _wait_socket(sock, task)
            client = SidecarClient(sock)
            member = RemoteMember("b0", client)
            try:
                resp = await member.manifest_hello(
                    manifest.to_json(), probe_keys=["p1", "p2", "p3"])
                assert resp["enabled"] and resp["agreed"]
                assert resp["digest"] == manifest.digest()
                assert resp["owners"] == manifest.owners(
                    ["p1", "p2", "p3"])
                view = {"a0": {"healthy": True, "draining": True,
                               "ts": 123.0}}
                gossip = await member.member_gossip(view)
                assert gossip["enabled"]
                assert gossip["digest"] == manifest.digest()
                assert gossip["view"]["a0"]["draining"] is True
            finally:
                await client.close()
                task.cancel()
                await asyncio.gather(task, return_exceptions=True)

        asyncio.run(scenario())

    def test_shard_transfer_stages_verified_bytes(self, data_dir,
                                                  tmp_path):
        """Warm plane bytes ship over the wire with their full region
        + routing identity; a corrupt body is a 400, never a cache
        entry (the plane_put posture)."""
        from omero_ms_image_region_tpu.io.devicecache import (
            plane_digest)
        from omero_ms_image_region_tpu.server.sidecar import (
            SidecarClient, run_sidecar)

        sock = str(tmp_path / "fed2.sock")
        arr = np.arange(8 * 8, dtype=np.uint16).reshape(8, 8)
        digest = plane_digest(arr)
        # One channel plane an entry: the key ends in ONE channel.
        entry = {"key": [IMG, 0, 0, 0, [0, 0, 8, 8], 1],
                 "digest": digest, "route": "route-xyz",
                 "dtype": "uint16", "shape": [8, 8],
                 "bytes": arr.tobytes()}

        async def scenario():
            task = asyncio.create_task(
                run_sidecar(_member_cfg(data_dir), sock))
            await _wait_socket(sock, task)
            client = SidecarClient(sock)
            member = RemoteMember("b0", client)
            try:
                # Corrupt digest first: refused, nothing staged.
                bad = dict(entry, digest="0" * 32)
                assert await member.shard_transfer([bad]) == 0
                staged = await member.shard_transfer([entry])
                assert staged == 1
                # The plane is resident by CONTENT on the receiver —
                # and by ROUTE (the explain/drain identity).
                status, body = await client.call(
                    "plane_probe", {}, extra={"digests": [digest]})
                assert status == 200
                assert json.loads(bytes(body).decode())["resident"] \
                    == [True]
                status, body = await client.call(
                    "explain", {}, extra={"key": "nope",
                                          "route": "route-xyz"})
                doc = json.loads(bytes(body).decode())
                assert doc.get("hbm") is True
                assert telemetry.FEDERATION.shard_transfers >= 1
            finally:
                await client.close()
                task.cancel()
                await asyncio.gather(task, return_exceptions=True)

        asyncio.run(scenario())

    def test_shard_transfer_retry_after_lost_ack_never_double_stages(
            self, data_dir, tmp_path):
        """The mid-transfer kill drill: the receiver stages the bytes
        but the CONNECTION dies before the ack reaches the sender (a
        killed process, a dropped link — the sender cannot tell).  The
        sender's retry re-ships the identical entry over a fresh
        connection; digest-dedup on the receiver makes the retry an
        idempotent success — exactly ONE staged copy, never two."""
        from omero_ms_image_region_tpu.io.devicecache import (
            plane_digest)
        from omero_ms_image_region_tpu.server.sidecar import (
            SidecarClient, run_sidecar)

        sock = str(tmp_path / "fed3.sock")
        arr = np.arange(8 * 8, dtype=np.uint16).reshape(8, 8)
        digest = plane_digest(arr)
        entry = {"key": [IMG, 0, 0, 0, [0, 0, 8, 8], 1],
                 "digest": digest, "route": "route-kill",
                 "dtype": "uint16", "shape": [8, 8],
                 "bytes": arr.tobytes()}

        async def scenario():
            task = asyncio.create_task(
                run_sidecar(_member_cfg(data_dir), sock))
            await _wait_socket(sock, task)
            client = SidecarClient(sock)
            try:
                # Leg 1: the bytes land and stage — then the link
                # dies before the sender consumes the ack.
                assert await RemoteMember(
                    "b0", client).shard_transfer([entry]) == 1
            finally:
                await client.close()
            retry_client = SidecarClient(sock)
            try:
                # Leg 2: the retry, byte-identical, fresh connection.
                # Idempotent success (the sender's ledger closes), not
                # a refusal and not a second copy.
                assert await RemoteMember(
                    "b0", retry_client).shard_transfer([entry]) == 1
                status, body = await retry_client.call(
                    "plane_probe", {}, extra={"digests": [digest]})
                assert status == 200
                assert json.loads(bytes(body).decode())["resident"] \
                    == [True]
                # The receiver's shard manifest holds exactly ONE
                # entry for the digest — the dedup contract.
                status, body = await retry_client.call(
                    "shard_manifest", {}, extra={})
                assert status == 200
                entries = json.loads(
                    bytes(body).decode())["entries"]
                assert sum(1 for e in entries
                           if e.get("digest") == digest) == 1
            finally:
                await retry_client.close()
                task.cancel()
                await asyncio.gather(task, return_exceptions=True)

        asyncio.run(scenario())


# ----------------------------------------------------------- coordinator

class _StubRemote:
    """Duck-typed RemoteMember for coordinator logic tests."""

    remote = True

    def __init__(self, name, hello=None, gossip=None):
        self.name = name
        self.healthy = True
        self.draining = False
        self.drain_intent = None
        self._hello = hello
        self._gossip = gossip
        self.marked_down = 0

    def mark_down(self):
        self.marked_down += 1
        self.healthy = False

    async def manifest_hello(self, doc, probe_keys=None):
        return self._hello(doc, probe_keys) if callable(self._hello) \
            else self._hello

    async def member_gossip(self, view):
        return self._gossip(view) if callable(self._gossip) \
            else self._gossip


class _StubRouterFor:
    def __init__(self, members):
        self.order = [m.name for m in members]
        self.members = {m.name: m for m in members}


class TestCoordinator:
    def _coord(self, manifest, *stubs):
        local = type("L", (), {"remote": False, "healthy": True,
                               "draining": False,
                               "drain_intent": None})()
        local.name = "a0"
        router = _StubRouterFor([local, *stubs])
        return FederationCoordinator(manifest, "hostA", router)

    def test_agree_verdicts(self):
        manifest = _manifest()
        my_owners = manifest.owners(list(federation.PROBE_KEYS))
        agreed = _StubRemote("b0", hello=lambda d, p: {
            "enabled": True, "agreed": True,
            "digest": manifest.digest(), "owners": my_owners})
        unreachable = _StubRemote("b1", hello=None)
        coord = self._coord(manifest, agreed, unreachable)
        verdicts = asyncio.run(coord.agree(strict=True))
        assert verdicts == {"b0": "agreed", "b1": "unreachable"}

    def test_agree_refuses_split_brain(self):
        manifest = _manifest()
        fork = _StubRemote("b0", hello={
            "enabled": True, "agreed": False,
            "reason": "split-brain"})
        coord = self._coord(manifest, fork)
        with pytest.raises(FederationError):
            asyncio.run(coord.agree(strict=True))
        assert asyncio.run(coord.agree(strict=False)) \
            == {"b0": "split-brain"}

    def test_agree_rejects_forged_probe_owners(self):
        """Digest agreement with WRONG probe owners is split-brain:
        the owners come from the peer's own ring math, and a
        disagreement there means shard maps fork in practice."""
        manifest = _manifest()
        wrong = list(reversed(manifest.owners(
            list(federation.PROBE_KEYS))))
        liar = _StubRemote("b0", hello={
            "enabled": True, "agreed": True,
            "digest": manifest.digest(), "owners": wrong})
        coord = self._coord(manifest, liar)
        with pytest.raises(FederationError):
            asyncio.run(coord.agree(strict=True))

    def test_agree_records_newer_epoch_pending_and_keeps_serving(self):
        """WE are the stale host mid-rollout: the peer's newer epoch
        lands PENDING (loud on status/summary), the active manifest —
        and therefore the live router's ring — stays what it was
        built with, and the strict join is tolerated."""
        manifest = _manifest(version=1)
        federation.install(manifest)
        newer = _manifest(version=4)
        peer = _StubRemote("b0", hello={
            "enabled": True, "agreed": False, "reason": "stale-epoch",
            "manifest": newer.to_json()})
        coord = self._coord(manifest, peer)
        verdicts = asyncio.run(coord.agree(strict=True))
        assert verdicts == {"b0": "stale"}
        assert coord.manifest.version == 1             # never swapped
        assert federation.current().version == 1
        assert federation.pending().version == 4
        assert coord.status()["pending_epoch"] == 4
        assert "pending roll" in coord.summary()

    def test_agree_tolerates_a_mixed_epoch_rollout_fleet(self):
        """A 3-host rollout in flight: TWO peers already run a newer
        epoch.  Both must verdict 'stale' (pending recorded once) and
        the strict join must still boot — a refused boot on a healthy
        rollout would turn every config change into an outage."""
        manifest = _manifest(version=1)
        federation.install(manifest)
        newer = _manifest(version=2)
        hello = {"enabled": True, "agreed": False,
                 "reason": "stale-epoch", "manifest": newer.to_json()}
        peers = [_StubRemote("b0", hello=dict(hello)),
                 _StubRemote("b1", hello=dict(hello))]
        coord = self._coord(manifest, *peers)
        verdicts = asyncio.run(coord.agree(strict=True))
        assert verdicts == {"b0": "stale", "b1": "stale"}
        assert federation.pending().version == 2
        # And the OLD-epoch peer's view of a NEWER joiner: pending is
        # a tolerated verdict too (the joiner must boot while old
        # hosts await their roll).
        pending_peer = _StubRemote("b2", hello={
            "enabled": True, "agreed": False, "reason": "pending",
            "pending_version": 2})
        coord2 = self._coord(manifest, pending_peer)
        assert asyncio.run(coord2.agree(strict=True)) \
            == {"b2": "pending"}

    def test_gossip_tolerates_the_pending_epochs_digest(self):
        """Mid-rollout gossip: a peer already running the epoch we
        hold PENDING is the expected state, not drift."""
        manifest = _manifest(version=1)
        federation.install(manifest)
        newer = _manifest(version=2)
        federation.set_pending(newer)
        peer = _StubRemote("b0", gossip={
            "enabled": True, "digest": newer.digest(), "view": {}})
        coord = self._coord(manifest, peer)
        assert asyncio.run(coord.gossip_once()) == {"b0": "ok"}

    def test_gossip_propagates_remote_drain_both_ways(self):
        import time as _time
        manifest = _manifest()
        now = _time.time()
        peer = _StubRemote("b0", gossip={
            "enabled": True, "digest": manifest.digest(),
            "view": {"b0": {"healthy": True, "draining": True,
                            "ts": now}}})
        coord = self._coord(manifest, peer)
        out = asyncio.run(coord.gossip_once())
        assert out == {"b0": "ok"}
        assert peer.draining is True            # drain propagated in
        peer._gossip = {
            "enabled": True, "digest": manifest.digest(),
            "view": {"b0": {"healthy": True, "draining": False,
                            "ts": now + 10}}}
        asyncio.run(coord.gossip_once())
        assert peer.draining is False           # ...and released

    def test_gossip_flags_manifest_drift(self):
        manifest = _manifest()
        peer = _StubRemote("b0", gossip={
            "enabled": True, "digest": "not-ours", "view": {}})
        coord = self._coord(manifest, peer)
        assert asyncio.run(coord.gossip_once()) == {"b0": "mismatch"}
        assert telemetry.FEDERATION.gossip.get("mismatch") == 1


# ----------------------------------- federated combined topology (app)

class TestFederatedCombinedApp:
    def _fed_config(self, data_dir, sock=None):
        members = [{"name": "a0", "host": "hostA"},
                   {"name": "a1", "host": "hostA"}]
        if sock:
            members.append({"name": "b0", "host": "hostB",
                            "address": sock})
        return AppConfig.from_dict({
            "data-dir": data_dir,
            "batcher": {"enabled": False},
            "raw-cache": {"enabled": True, "prefetch": False},
            "renderer": {"cpu-fallback-max-px": 0},
            "image-region-cache": {"enabled": True},
            "federation": {
                "enabled": True, "host": "hostA", "shard-epoch": 1,
                "ring-seed": "fed-app",
                "members": members},
        })

    def test_all_local_federation_serves_and_reports(self, data_dir):
        """A one-host federation (both members local) builds, serves,
        annotates /readyz and answers /admin/federation."""
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.server.app import create_app

        async def scenario():
            app = create_app(self._fed_config(data_dir))
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                r = await client.get(
                    f"/webgateway/render_image_region/{IMG}/0/0"
                    f"?tile=0,0,0,32,32&format=png&m=g"
                    f"&c=1|0:60000$FF0000")
                assert r.status == 200 and await r.read()
                r = await client.get("/admin/federation")
                doc = await r.json()
                assert r.status == 200
                assert doc["epoch"] == 1
                assert [m["name"] for m in doc["members"]] \
                    == ["a0", "a1"]
                r = await client.get("/readyz")
                doc = await r.json()
                assert "federation" in doc["checks"]
            finally:
                await client.close()

        asyncio.run(scenario())

    def test_combined_role_peer_byte_fetch_over_the_wire(
            self, data_dir, tmp_path):
        """The PR 11 follow-on: in a MIXED federated topology the
        combined role's byte-tier authority probe crosses the wire —
        a plane whose ring authority is the remote host serves from
        ITS byte tier (peer fetch, zero local renders) when routing
        re-homes, exactly the RemoteMember-fleet contract."""
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.server.app import (
            FLEET_ROUTER_KEY, create_app)
        from omero_ms_image_region_tpu.server.sidecar import (
            run_sidecar)
        from omero_ms_image_region_tpu.utils.stopwatch import (
            REGISTRY as SPAN_REG)

        sock = str(tmp_path / "b0.sock")
        sidecar_cfg = AppConfig(
            data_dir=data_dir,
            batcher=BatcherConfig(enabled=False),
            raw_cache=RawCacheConfig(enabled=True, prefetch=False),
            renderer=RendererConfig(cpu_fallback_max_px=0))
        from omero_ms_image_region_tpu.server.config import (
            CacheConfig)
        sidecar_cfg.caches = CacheConfig.enabled_all()

        def renders():
            snap = SPAN_REG.snapshot()
            return (snap.get("Renderer.renderAsPackedInt",
                             {}).get("count", 0)
                    + snap.get("Renderer.renderAsPackedInt.cpu",
                               {}).get("count", 0))

        async def scenario():
            task = asyncio.create_task(run_sidecar(sidecar_cfg, sock))
            await _wait_socket(sock, task)
            app = create_app(self._fed_config(data_dir, sock=sock))
            client = TestClient(TestServer(app))
            await client.start_server()
            router = app[FLEET_ROUTER_KEY]
            try:
                assert any(getattr(m, "remote", False)
                           for m in router.members.values())
                # Find tiles whose ring owner is the REMOTE member.
                owned = []
                for x in range(2):
                    for y in range(2):
                        ctx = ImageRegionCtx.from_params(
                            _params(x, y), None)
                        if router.owner_of(ctx) == "b0":
                            owned.append((x, y))
                assert owned, "remote member owns nothing here"
                url = (f"/webgateway/render_image_region/{IMG}/0/0"
                       f"?tile=0,{owned[0][0]},{owned[0][1]},32,32"
                       f"&format=png&m=g&c=1|0:60000$FF0000")
                r = await client.get(url)
                body = await r.read()
                assert r.status == 200 and body
                # Drain the remote owner: the next request re-homes
                # to a LOCAL member, which must serve the DRAINING
                # authority's bytes over byte_fetch — no re-render.
                await router.drain_member("b0", prestage=False,
                                          settle_timeout_s=5.0)
                before = renders()
                hits0 = telemetry.HTTPCACHE.peer_hits
                r = await client.get(url)
                body2 = await r.read()
                assert r.status == 200 and body2 == body
                assert renders() == before
                assert telemetry.HTTPCACHE.peer_hits == hits0 + 1
            finally:
                await client.close()
                task.cancel()
                await asyncio.gather(task, return_exceptions=True)

        asyncio.run(scenario())


# --------------------------------------------- shard-aware prefetch

class TestRemotePrestage:
    def test_router_hints_the_remote_owner(self):
        class _Hinted(_StubRemote):
            def __init__(self, name):
                super().__init__(name)
                self.entries = []

            async def prestage_manifest(self, entries):
                self.entries += entries
                return len(entries)

        remote = _Hinted("b0")
        router = FleetRouter([remote], lane_width=1)
        entry = {"key": [1, 0, 0, 0, [0, 0, 32, 32], [1]],
                 "route": "r1"}

        async def scenario():
            assert router.remote_prestage_for_route("r1", entry)
            await asyncio.gather(*router._putback_tasks,
                                 return_exceptions=True)

        asyncio.run(scenario())
        assert remote.entries == [entry]
        assert telemetry.FEDERATION.remote_prestage == 1

    def test_local_owner_is_not_hinted(self, data_dir):
        from omero_ms_image_region_tpu.parallel.fleet import (
            build_local_members)
        from omero_ms_image_region_tpu.server.app import build_services
        config = _member_cfg(data_dir)
        services = build_services(config)
        try:
            members = build_local_members(config, services, 2)
            router = FleetRouter(members)
            assert router.remote_prestage_for_route(
                "any-route", {"key": [1, 0, 0, 0, [0, 0, 1, 1],
                                      [1]]}) is False
        finally:
            services.pixels_service.close()


# ------------------------------------------------- bench gate plumbing

class TestMultichipGateAcceptsFederatedRecords:
    def test_fed_keys_judged_and_legacy_skips(self, tmp_path):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "scripts"))
        import bench_gate

        old = {"metric": "multichip",
               "fleet_tiles_per_sec_m4": 100.0,
               "fleet_tiles_per_sec_m8": 150.0,
               "fleet_scaling_efficiency": 0.8}
        new = dict(old, fed_tiles_per_sec_p2=50.0,
                   fed_process_scaling_efficiency=0.7)
        (tmp_path / "MULTICHIP_r01.json").write_text(json.dumps(old))
        (tmp_path / "MULTICHIP_r02.json").write_text(json.dumps(new))
        rc = bench_gate.main(["--multichip", "--dir", str(tmp_path)])
        assert rc == 0          # legacy round lacks fed keys: skip

        worse = dict(new, fed_tiles_per_sec_p2=30.0)
        (tmp_path / "MULTICHIP_r03.json").write_text(json.dumps(worse))
        rc = bench_gate.main(["--multichip", "--dir", str(tmp_path)])
        assert rc != 0          # 50 -> 30 is a fed-key regression


# --------------------------------------------- THE multihost smoke

class TestMultihostSmoke:
    """THE acceptance drill: a TWO-PROCESS federated fleet.  Two real
    spawned sidecar processes (hostA / hostB), one agreed manifest:

    1. both processes agree on the manifest digest AND assign every
       golden probe key to the same owner, each from its OWN ring;
    2. one member process dies mid-serving — its shard fails over
       ring-next with zero 5xx-without-shed;
    3. a cross-host drain completes with warm handoff, and the
       successor answers the drained working set without the dead
       member.
    """

    @pytest.fixture()
    def fleet(self, data_dir, tmp_path):
        import yaml

        from omero_ms_image_region_tpu.server.sidecar import (
            spawn_sidecar)

        socks = [str(tmp_path / f"fed-{h}.sock")
                 for h in ("a", "b")]
        members = [
            {"name": "fa0", "host": "hostA", "address": socks[0]},
            {"name": "fb0", "host": "hostB", "address": socks[1]},
        ]
        procs = []
        try:
            for host, sock in zip(("hostA", "hostB"), socks):
                cfg = {
                    "data-dir": data_dir,
                    "batcher": {"enabled": False},
                    "raw-cache": {"enabled": True, "prefetch": False,
                                  "digest-dedup": True},
                    "renderer": {"cpu-fallback-max-px": 0},
                    "image-region-cache": {"enabled": True},
                    "federation": {
                        "enabled": True, "host": host,
                        "shard-epoch": 1, "ring-seed": "smoke",
                        "members": members},
                }
                path = str(tmp_path / f"cfg-{host}.yaml")
                with open(path, "w") as f:
                    yaml.safe_dump(cfg, f)
                procs.append(spawn_sidecar(path, sock))
            yield socks, members, procs
        finally:
            for proc in procs:
                if proc.poll() is None:
                    proc.terminate()
            for proc in procs:
                try:
                    proc.wait(timeout=15)
                except Exception:
                    proc.kill()

    def test_two_process_fleet_agrees_survives_death_and_drains(
            self, fleet, data_dir):
        from omero_ms_image_region_tpu.server.sidecar import (
            SidecarClient)

        socks, member_specs, procs = fleet
        manifest = FleetManifest(
            [MemberSpec(m["name"], m["host"], m["address"])
             for m in member_specs],
            version=1, ring_seed="smoke")

        async def scenario():
            members = [
                RemoteMember(m["name"],
                             SidecarClient(m["address"],
                                           breaker=None),
                             down_cooldown_s=30.0)
                for m in member_specs]
            router = FleetRouter(members, lane_width=2,
                                 steal_min_backlog=0,
                                 ring_seed=manifest.ring_seed)
            handler = FleetImageHandler(
                router, single_flight=SingleFlight())
            coord = FederationCoordinator(manifest, "fe-host", router)
            try:
                # ---- 1. agreement, against each process's own ring.
                verdicts = await coord.agree(strict=True)
                assert verdicts == {"fa0": "agreed", "fb0": "agreed"}
                probe_owner_sets = []
                for member in members:
                    resp = await member.manifest_hello(
                        manifest.to_json(),
                        probe_keys=list(federation.PROBE_KEYS))
                    probe_owner_sets.append(tuple(resp["owners"]))
                # Same plane_route_key -> same owner in BOTH
                # processes (and in this one).
                assert probe_owner_sets[0] == probe_owner_sets[1] \
                    == tuple(manifest.owners(
                        list(federation.PROBE_KEYS)))

                # ---- serve a working set; remember bytes + owners.
                tiles = [(x, y) for x in range(4)
                         for y in range(4)]
                bodies = {}
                owners = {}
                for (x, y) in tiles:
                    ctx = ImageRegionCtx.from_params(
                        _params(x, y, edge=16), None)
                    owners[(x, y)] = router.owner_of(ctx)
                    ctx2 = ImageRegionCtx.from_params(
                        _params(x, y, edge=16), None)
                    bodies[(x, y)] = await \
                        handler.render_image_region(ctx2)
                    assert bodies[(x, y)]
                assert set(owners.values()) == {"fa0", "fb0"}, \
                    "grid too small: one member owns everything"

                # ---- 2. kill hostB's PROCESS mid-serving.
                procs[1].kill()
                procs[1].wait(timeout=10)
                survivors = 0
                for (x, y) in tiles:
                    ctx = ImageRegionCtx.from_params(
                        _params(x, y, edge=16), None)
                    data = await handler.render_image_region(ctx)
                    assert data, (x, y)     # zero 5xx-without-shed:
                    # every request still yields bytes
                    survivors += 1
                assert survivors == len(tiles)
                assert not router.members["fb0"].healthy
                assert telemetry.FLEET.totals()["failed_over"] >= 1

                # ---- 3. cross-host drain with warm handoff: drain
                # the SURVIVOR'S peer fa0... fb0 is dead, so drain
                # fa0's shard onto... nothing remote remains.  Use
                # the live pair instead: undo the death by treating
                # fa0 as the drain SOURCE and fb0's replacement as
                # target is impossible — so this leg drains fa0 with
                # fb0 restarted.
                from omero_ms_image_region_tpu.server.sidecar import (
                    spawn_sidecar)
                import yaml  # noqa: F401  (fixture wrote configs)
                procs[1] = spawn_sidecar(
                    os.path.join(os.path.dirname(socks[1]),
                                 "cfg-hostB.yaml"), socks[1])
                router.members["fb0"].revive()
                # fa0's HBM shard (hinted manifest) hands to fb0 on
                # drain; fb0 re-reads from the shared store and the
                # working set serves with fa0 DRAINING, zero errors.
                doc = await router.drain_member(
                    "fa0", settle_timeout_s=10.0)
                assert doc["planes"] >= 1
                assert doc["prestaged"] >= 1
                for (x, y) in tiles:
                    ctx = ImageRegionCtx.from_params(
                        _params(x, y, edge=16), None)
                    data = await handler.render_image_region(ctx)
                    assert data == bodies[(x, y)], (x, y)
                router.undrain_member("fa0")
            finally:
                await router.close()
                for member in members:
                    await member.client.close()

        asyncio.run(scenario())


# ------------------------------------------------------------- metrics

class TestFederationMetrics:
    def test_emit_when_live_reset_and_closed_reasons(self):
        """Emit-when-live (non-federated expositions stay exact), the
        closed reason vocabularies, the robustness_metric_lines ride,
        and the reset() contract."""
        assert telemetry.FEDERATION.metric_lines() == []
        assert not any("federation" in line for line in
                       telemetry.robustness_metric_lines())
        telemetry.FEDERATION.set_manifest(3, 4)
        telemetry.FEDERATION.count_agreement("agreed")
        telemetry.FEDERATION.count_agreement("no-such-reason")
        telemetry.FEDERATION.count_gossip("ok")
        telemetry.FEDERATION.count_transfer(1024)
        telemetry.FEDERATION.count_remote_prestage()
        lines = telemetry.FEDERATION.metric_lines()
        assert "imageregion_federation_manifest_version 3" in lines
        assert "imageregion_federation_members 4" in lines
        assert ("imageregion_federation_shard_transfers_total 1"
                in lines)
        assert ("imageregion_federation_transfer_bytes_total 1024"
                in lines)
        assert ("imageregion_federation_agreements_total"
                '{reason="agreed"} 1' in lines)
        # Caller-minted reasons clamp to the closed vocabulary.
        assert ("imageregion_federation_agreements_total"
                '{reason="unreachable"} 1' in lines)
        assert any("federation" in line for line in
                   telemetry.robustness_metric_lines())
        # Every family is TYPE-registered (the exposition finalizer
        # asserts HELP/TYPE-once over these).
        for line in lines:
            fam = line.split("{")[0].split(" ")[0]
            assert fam in telemetry.METRIC_TYPES, fam
        telemetry.reset()
        assert telemetry.FEDERATION.metric_lines() == []


class TestGossipDrainOwnership:
    def test_gossip_never_reverts_a_drain_this_router_ordered(self):
        """Host A drains remote member b0 (operator or autoscaler).
        Host B — never told — gossips b0 {draining: false}.  The
        drain must STAND: reverting it would undo every cross-host
        scale-down/operator drain within one gossip interval (and
        corrupt the autoscaler's park accounting)."""
        import time as _time
        manifest = _manifest()
        now = _time.time()
        peer = _StubRemote("b0", gossip={
            "enabled": True, "digest": manifest.digest(),
            "view": {"b0": {"healthy": True, "draining": False,
                            "ts": now + 60}}})
        # OUR drain, autoscale intent (the scale-down posture).
        peer.draining = True
        peer.drain_intent = "autoscale"
        coord = self._coord(manifest, peer)
        assert asyncio.run(coord.gossip_once()) == {"b0": "ok"}
        assert peer.draining is True              # drain stands
        assert peer.drain_intent == "autoscale"

    def test_gossip_set_drains_carry_gossip_intent_and_clear(self):
        """Peer-reported drains land under the 'gossip' intent (so
        drain.fail-readyz never pulls THIS instance for ANOTHER
        host's roll) and the same peer's newer all-clear releases
        them."""
        import time as _time
        manifest = _manifest()
        now = _time.time()
        peer = _StubRemote("b0", gossip={
            "enabled": True, "digest": manifest.digest(),
            "view": {"b0": {"healthy": True, "draining": True,
                            "ts": now}}})
        coord = self._coord(manifest, peer)
        asyncio.run(coord.gossip_once())
        assert peer.draining and peer.drain_intent == "gossip"
        peer._gossip = {
            "enabled": True, "digest": manifest.digest(),
            "view": {"b0": {"healthy": True, "draining": False,
                            "ts": now + 5}}}
        asyncio.run(coord.gossip_once())
        assert not peer.draining and peer.drain_intent is None

    _coord = TestCoordinator._coord

    def test_merge_view_drops_names_outside_the_manifest(self):
        """The merged view is bounded by the MEMBERSHIP: the socket
        is unauthenticated by design and the view re-broadcasts in
        every gossip answer, so unknown names must die at the merge,
        not live in the module-global forever."""
        federation.install(_manifest())
        merged = federation.merge_view({
            "b0": {"healthy": True, "ts": 1.0},
            "intruder": {"healthy": False, "ts": 2.0}})
        assert "b0" in merged and "intruder" not in merged


# ------------------------------------------- versioned gossip & jitter

def _local_member(name):
    m = type("L", (), {"remote": False, "healthy": True,
                       "draining": False, "drain_intent": None})()
    m.name = name
    return m


class TestVersionedGossip:
    def test_skewed_ahead_peer_cannot_pin_a_stale_down_verdict(self):
        """THE clock-skew regression (the bug versioning replaced):
        under newest-ts-wins, a peer whose wall clock ran years ahead
        could relay a stale ``down`` observation stamped in the future
        and no honest update would ever outrank it.  Versioned merges
        order on ``(incarnation, seq)`` — a legacy ts-only observation
        compares as ``(0, ts)`` and ANY versioned truth beats it, no
        matter the timestamp."""
        import time as _time
        federation.install(_manifest(), self_host="hostA")
        router = _StubRouterFor([_local_member("a0"),
                                 _local_member("a1")])
        # The skewed-ahead ghost: a0 "down", stamped 3 years ahead.
        federation.merge_view({"a0": {
            "healthy": False, "ts": _time.time() + 1e8}})
        view = federation.local_view(router, "hostA")
        merged = federation.merge_view(view)
        assert merged["a0"]["healthy"] is True, \
            "a future-stamped stale observation outranked the live " \
            "router state — the newest-ts-wins bug is back"

    def test_self_refutation_outranks_a_versioned_ghost(self):
        """The SWIM rejoin rule: a HIGHER-versioned observation about
        one of our own members that disagrees with the live router
        (a pre-restart ghost of ourselves, relayed back) forces an
        incarnation bump past it — the fresh truth supersedes
        fleet-wide instead of losing the version race."""
        federation.install(_manifest(), self_host="hostA")
        router = _StubRouterFor([_local_member("a0"),
                                 _local_member("a1")])
        inc0 = federation.local_view(router, "hostA")["a0"]["inc"]
        federation.merge_view({"a0": {
            "healthy": False, "inc": inc0 + 50, "seq": 99, "ts": 0}})
        view = federation.local_view(router, "hostA")
        assert view["a0"]["inc"] > inc0 + 50
        merged = federation.merge_view(view)
        assert merged["a0"]["healthy"] is True

    def test_gossip_tick_jitter_is_seeded_and_spread(self):
        """The tick interval jitters within +/-20% so an N-host
        fleet's gossip bursts cannot synchronize into a thundering
        herd — and the jitter is SEEDED per (host, ring seed), so a
        drill's schedule replays bit-exactly."""
        manifest = _manifest()
        coord = FederationCoordinator(manifest, "hostA", router=None,
                                      gossip_interval_s=1.0,
                                      handles=[])
        samples = [coord.next_interval_s() for _ in range(64)]
        assert all(0.8 <= s <= 1.2 for s in samples), samples
        assert max(samples) - min(samples) > 0.05, \
            "jitter collapsed — gossip ticks would synchronize"
        # Seeded: the same (host, ring seed) replays the schedule.
        again = FederationCoordinator(manifest, "hostA", router=None,
                                      gossip_interval_s=1.0,
                                      handles=[])
        assert [again.next_interval_s() for _ in range(64)] == samples
        # Different hosts de-phase from each other.
        other = FederationCoordinator(manifest, "hostB", router=None,
                                      gossip_interval_s=1.0,
                                      handles=[])
        assert [other.next_interval_s()
                for _ in range(64)] != samples


# ------------------------------------------------------ quorum fencing

def _manifest3(version=1, seed="fed-test"):
    return FleetManifest(
        [MemberSpec("a0", "hostA"),
         MemberSpec("b0", "hostB", "10.0.0.2:8476"),
         MemberSpec("c0", "hostC", "10.0.0.3:8476")],
        version=version, ring_seed=seed)


class TestQuorumFencing:
    def test_gates_default_open_without_a_tracker(self):
        """Quorum off (the default) is bit-exact pre-quorum behavior:
        every gate answers True, nothing is fenced, status is None."""
        assert federation.quorum_tracker() is None
        assert federation.is_fenced() is False
        assert federation.quorum_allow("adoption") is True
        assert federation.quorum_status() is None

    def test_fence_restore_transitions_ledger_and_refusals(self):
        """Losing a strict majority FENCES (one ledger record, one
        flight event, refusals counted per action); regaining it
        RESTORES with the refusal tally on the restore record.
        Liveness runs on an injected monotonic clock — wall time
        never participates."""
        from omero_ms_image_region_tpu.utils import decisions
        decisions.LEDGER.reset()
        now = [100.0]
        tracker = federation.QuorumTracker(
            _manifest3(), "hostA", suspect_after_s=5.0,
            clock=lambda: now[0])
        federation.install_quorum(tracker)
        # Boot grace: remote hosts start heard-now — no fence at boot.
        assert federation.is_fenced() is False
        # Silence past the suspect window from BOTH peers: 1/3 is a
        # minority island.
        now[0] += 6.0
        assert federation.is_fenced() is True
        assert federation.quorum_allow("adoption") is False
        assert federation.quorum_allow("write_authority") is False
        status = federation.quorum_status()
        assert status["fenced"] is True
        assert status["refusals"] == {"adoption": 1,
                                      "write_authority": 1}
        # One heard host restores the majority (2/3).
        federation.observe_host("hostB")
        assert federation.is_fenced() is False
        kinds = [(r["kind"], r["verdict"])
                 for r in decisions.LEDGER.snapshot()]
        assert ("quorum", "fenced") in kinds
        assert ("quorum", "restored") in kinds
        restored = [r for r in decisions.LEDGER.snapshot()
                    if r["verdict"] == "restored"][-1]
        assert restored["detail"]["refusals"] == {
            "adoption": 1, "write_authority": 1}
        assert restored["detail"]["fenced_s"] == 0.0
        flight = [e["kind"] for e in telemetry.FLIGHT.snapshot()]
        assert "quorum.fence" in flight
        assert "quorum.restore" in flight

    def test_single_host_manifest_is_always_quorate(self):
        now = [0.0]
        tracker = federation.QuorumTracker(
            FleetManifest([MemberSpec("a0", "hostA")], version=1),
            "hostA", suspect_after_s=1.0, clock=lambda: now[0])
        now[0] += 100.0
        assert tracker.evaluate() is True

    def test_two_of_three_hosts_is_quorate(self):
        now = [0.0]
        tracker = federation.QuorumTracker(
            _manifest3(), "hostA", suspect_after_s=5.0,
            clock=lambda: now[0])
        now[0] += 6.0
        tracker.observe("hostB")       # heard one of two peers
        assert tracker.evaluate() is True
        assert tracker.reachable_hosts() == ["hostB"]

    def test_rolled_manifest_reshapes_the_host_set(self):
        """set_manifest on an epoch roll: departed hosts leave the
        denominator (a 3-host fleet rolled to 2 hosts must not fence
        because the removed host is silent forever)."""
        now = [0.0]
        tracker = federation.QuorumTracker(
            _manifest3(), "hostA", suspect_after_s=5.0,
            clock=lambda: now[0])
        two_hosts = FleetManifest(
            [MemberSpec("a0", "hostA"),
             MemberSpec("b0", "hostB", "10.0.0.2:8476")],
            version=2, ring_seed="fed-test")
        tracker.set_manifest(two_hosts)
        now[0] += 6.0
        tracker.observe("hostB")
        assert tracker.evaluate() is True
        assert "hostC" not in tracker.reachable_hosts()


# ------------------------------------------------- orchestrated rolls

class _RollStub(_StubRemote):
    """_StubRemote + the two-phase roll wire methods."""

    def __init__(self, name, propose=None, commit=None, **kw):
        super().__init__(name, **kw)
        self._propose = propose
        self._commit = commit
        self.proposed = []
        self.committed = []

    async def epoch_propose(self, doc):
        self.proposed.append(doc)
        return self._propose(doc) if callable(self._propose) \
            else self._propose

    async def epoch_commit(self, doc, digest=""):
        self.committed.append((doc, digest))
        return self._commit(doc) if callable(self._commit) \
            else self._commit


class TestEpochRoll:
    def _coord(self, manifest, *stubs):
        router = _StubRouterFor([_local_member("a0"), *stubs])
        return FederationCoordinator(manifest, "hostA", router)

    def test_roll_commits_on_strict_majority(self):
        """Two-phase roll with one host dark: propose acks from A
        (self) + B beat 3 hosts' majority bar, commit activates
        everywhere reachable, the roll hook swaps the live ring at
        COMMIT (the only mid-flight ring change), and the flight ring
        carries the propose/commit pair."""
        manifest = _manifest3()
        federation.install(manifest, self_host="hostA")
        swapped = []
        federation.set_roll_hook(swapped.append)
        b0 = _RollStub("b0",
                       propose={"ack": True, "reason": "pending",
                                "host": "hostB"},
                       commit={"ack": True, "reason": "installed",
                               "host": "hostB"})
        c0 = _RollStub("c0", propose=None, commit=None)
        coord = self._coord(manifest, b0, c0)
        rolled = _manifest3(version=2, seed="fed-test-v2")
        out = asyncio.run(coord.roll_epoch(rolled))
        assert out["committed"] is True
        assert out["acks"] == 2 and out["hosts"] == 3
        assert out["verdicts"]["hostB"] == "installed"
        assert out["verdicts"]["hostC"] == "unreachable"
        # Commit went to every reachable host, with the digest pinned.
        assert b0.committed[0][1] == rolled.digest()
        # Activated locally + the serving-layer hook fired once.
        assert federation.current().version == 2
        assert coord.manifest.version == 2
        assert [m.version for m in swapped] == [2]
        flight = [e["kind"] for e in telemetry.FLIGHT.snapshot()]
        assert "epoch.propose" in flight
        assert "epoch.commit" in flight

    def test_roll_aborts_without_strict_majority(self):
        """Both remote hosts dark: 1/3 acks is not a strict majority
        — NOTHING activates anywhere (a minority can never advance
        the epoch)."""
        manifest = _manifest3()
        federation.install(manifest, self_host="hostA")
        swapped = []
        federation.set_roll_hook(swapped.append)
        b0 = _RollStub("b0", propose=None, commit=None)
        c0 = _RollStub("c0", propose=None, commit=None)
        coord = self._coord(manifest, b0, c0)
        out = asyncio.run(coord.roll_epoch(_manifest3(version=2)))
        assert out["committed"] is False and out["acks"] == 1
        assert federation.current().version == 1
        assert coord.manifest.version == 1
        assert swapped == []
        assert b0.committed == [] and c0.committed == []

    def test_fenced_coordinator_refuses_to_roll(self):
        """A fenced minority cannot know whether the majority already
        rolled past it — originating an epoch from the island is the
        split-brain the fence exists to prevent."""
        manifest = _manifest3()
        federation.install(manifest, self_host="hostA")
        now = [0.0]
        federation.install_quorum(federation.QuorumTracker(
            manifest, "hostA", suspect_after_s=1.0,
            clock=lambda: now[0]))
        now[0] += 5.0                  # both peers silent: fenced
        coord = self._coord(manifest, _RollStub(
            "b0", propose={"ack": True}, commit={"ack": True}))
        out = asyncio.run(coord.roll_epoch(_manifest3(version=2)))
        assert out["committed"] is False
        assert out.get("reason") == "fenced"
        assert federation.current().version == 1

    def test_roll_must_raise_the_version(self):
        manifest = _manifest3(version=3)
        federation.install(manifest, self_host="hostA")
        coord = self._coord(manifest, _RollStub("b0"))
        with pytest.raises(ValueError):
            asyncio.run(coord.roll_epoch(_manifest3(version=3)))

    def test_crash_resumed_roll_is_idempotent_wire_side(self):
        """The receiver contract that makes coordinator crash-resume
        safe: re-propose of the pending epoch acks again; commit
        activates once; re-commit and late re-propose of the
        now-active epoch ack ``already-active``; a superseded (older)
        commit refuses ``stale``; a forged commit digest refuses."""
        federation.install(_manifest3(), self_host="hostB")
        v2 = _manifest3(version=2)
        doc = v2.to_json()
        first = federation.handle_epoch_propose({"manifest": doc})
        again = federation.handle_epoch_propose({"manifest": doc})
        assert first["ack"] and again["ack"]
        assert again["reason"] == "pending"
        assert federation.current().version == 1      # nothing active
        forged = federation.handle_epoch_commit(
            {"manifest": doc, "digest": "0" * 32})
        assert forged["ack"] is False
        assert forged["reason"] == "digest-mismatch"
        committed = federation.handle_epoch_commit(
            {"manifest": doc, "digest": v2.digest()})
        assert committed["ack"] and committed["reason"] == "installed"
        assert federation.current().version == 2
        assert federation.pending() is None           # superseded
        re_commit = federation.handle_epoch_commit({"manifest": doc})
        assert re_commit["ack"]
        assert re_commit["reason"] == "already-active"
        late = federation.handle_epoch_propose({"manifest": doc})
        assert late["ack"] and late["reason"] == "already-active"
        stale = federation.handle_epoch_commit(
            {"manifest": _manifest3(version=1).to_json()})
        assert stale["ack"] is False and stale["reason"] == "stale"
        assert federation.current().version == 2

    def test_fenced_receiver_refuses_propose(self):
        manifest = _manifest3()
        federation.install(manifest, self_host="hostC")
        now = [0.0]
        federation.install_quorum(federation.QuorumTracker(
            manifest, "hostC", suspect_after_s=1.0,
            clock=lambda: now[0]))
        now[0] += 5.0
        out = federation.handle_epoch_propose(
            {"manifest": _manifest3(version=2).to_json()})
        assert out["ack"] is False and out["reason"] == "fenced"
        # The commit still lands: it is the anti-entropy path a
        # healed (restored) host converges through.
        federation.observe_host("hostA")
        v2 = _manifest3(version=2)
        out = federation.handle_epoch_commit(
            {"manifest": v2.to_json(), "digest": v2.digest()})
        assert out["ack"] and federation.current().version == 2

    def test_coordinator_adopts_a_wire_committed_epoch(self):
        """A sidecar's coordinator whose manifest a wire-side commit
        outran (handle_epoch_commit swapped the process-global) must
        gossip the COMMITTED identity from the next round on — not
        advertise the pre-roll digest forever."""
        manifest = _manifest3()
        federation.install(manifest, self_host="hostA")
        v2 = _manifest3(version=2)
        b0 = _RollStub("b0", gossip=lambda view: {
            "enabled": True, "version": 2, "digest": v2.digest(),
            "view": {}})
        coord = self._coord(manifest, b0)
        assert coord.manifest.version == 1
        federation.handle_epoch_commit(
            {"manifest": v2.to_json(), "digest": v2.digest()})
        outcome = asyncio.run(coord.gossip_once())
        assert coord.manifest.version == 2
        assert outcome["b0"] == "ok"       # no phantom drift
