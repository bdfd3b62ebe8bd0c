"""Fid-lease failover semantics across a 3-master raft quorum.

Satellite coverage for master/lease.py under the HA control plane:

- the lease registry is rebuilt from the raft log on EVERY master, so
  whichever follower wins the next election already carries the live
  grants and `SeaweedFS_fid_leases_active` stays correct after failover;
- expired-but-unreplayed grants are never REISSUED: key uniqueness
  lives in the replicated sequencer high-water mark, not the registry,
  so a new leader's fresh leases are disjoint from every range an old
  leader ever acked — even ranges whose lease TTL lapsed unused;
- followers serve /dir/lookup for leased volumes from the replicated
  vid cache once the leader's KeepConnected feed reaches them.
"""

import socket
import time

import pytest
import requests

from conftest import wait_until
from seaweedfs_tpu.client.master_client import MasterClient
from seaweedfs_tpu.master.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.storage.disk_location import DiskLocation
from seaweedfs_tpu.storage.store import Store


def _fp():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_for_leader(masters, timeout=10.0):
    out = []

    def one_leader():
        out[:] = [m for m in masters if m.is_leader and not m._stop.is_set()]
        return len(out) == 1

    wait_until(one_leader, timeout=timeout,
               msg=f"single leader among {[m.address for m in masters]}")
    return out[0]


@pytest.fixture()
def ha_cluster(tmp_path):
    """3-master quorum (gRPC + HTTP), one volume server heartbeating
    whoever leads, and a client that knows every master."""
    ports = [_fp() for _ in range(3)]
    peers = [f"127.0.0.1:{p}" for p in ports]
    masters = []
    for p in ports:
        ms = MasterServer(port=p, http_port=_fp(), volume_size_limit_mb=64,
                          pulse_seconds=0.3, peers=peers,
                          raft_state_path=str(tmp_path / f"raft-{p}.json"))
        ms.start()
        masters.append(ms)
    leader = _wait_for_leader(masters)
    all_addrs = ",".join(m.address for m in masters)
    vport = _fp()
    store = Store("127.0.0.1", vport, "",
                  [DiskLocation(str(tmp_path / "vols"), max_volume_count=8)],
                  coder_name="numpy")
    vs = VolumeServer(store, all_addrs, port=vport, grpc_port=_fp(),
                      pulse_seconds=0.3)
    vs.start()
    wait_until(lambda: len(leader.topo.nodes) >= 1, msg="vs registered")
    mc = MasterClient(all_addrs).start()
    mc.wait_connected()
    yield masters, vs, mc
    mc.stop()
    vs.stop()
    for m in masters:
        m.stop()


def _live(masters):
    return [m for m in masters if not m._stop.is_set()]


def test_lease_registry_replicates_to_all_masters(ha_cluster):
    """One lease_fids round-trip on the leader lands the grant in every
    master's registry (and hence the leases-active gauge, wherever the
    next scrape or election happens)."""
    masters, _vs, mc = ha_cluster
    lease = mc.lease_fids(64)
    assert lease.remaining() == 64
    wait_until(lambda: all(m.fid_leases.active() >= 1 for m in masters),
               timeout=5, msg="lease grant replicated to all registries")
    from seaweedfs_tpu.stats import FID_LEASES_ACTIVE
    assert FID_LEASES_ACTIVE.value() >= 1
    # the replicated high-water mark moved past the granted range
    assert all(m.sequencer.peek >= lease.end_key for m in masters)


def test_failover_registry_rebuilt_and_ranges_disjoint(ha_cluster):
    """Kill the leader mid-lease-window: the new leader's registry still
    shows the outstanding grant, and the ranges it leases next never
    overlap anything the dead leader acked."""
    masters, _vs, mc = ha_cluster
    leader = _wait_for_leader(masters)
    old = mc.lease_fids(128)
    wait_until(lambda: all(m.fid_leases.active() >= 1 for m in masters),
               timeout=5, msg="grant replicated before failover")

    leader.stop()
    new_leader = _wait_for_leader(_live(masters))
    # registry rebuilt from the raft log: the grant is live on the new
    # leader without anyone re-asking
    assert new_leader.fid_leases.active() >= 1
    from seaweedfs_tpu.stats import FID_LEASES_ACTIVE
    assert FID_LEASES_ACTIVE.value() >= 1
    # the committed hwm survived the failover
    assert new_leader.sequencer.peek >= old.end_key

    wait_until(lambda: len(new_leader.topo.nodes) >= 1, timeout=15,
               msg="vs re-registered with new leader")
    deadline = time.time() + 15
    new = None
    while time.time() < deadline:
        try:
            new = mc.lease_fids(128)
            break
        except Exception:  # noqa: BLE001 — client chases the new leader
            time.sleep(0.3)
    assert new is not None, "lease after failover never succeeded"
    # zero duplicate fids: disjoint key ranges across the leader change
    assert new.next_key >= old.end_key or new.vid != old.vid


def test_expired_unreplayed_grant_never_reissued(ha_cluster):
    """A grant whose TTL lapses before (or after) a failover must expire
    OUT of the registry — but its key range must never come back: the
    sequencer hwm is replicated, the registry is advisory."""
    masters, _vs, _mc = ha_cluster
    leader = _wait_for_leader(masters)
    hwm = leader.sequencer.peek + 4096
    assert leader.raft.propose(
        {"seq_hwm": hwm, "lease": {"count": 4096, "ttl_s": 0.2}})
    wait_until(lambda: all(m.sequencer.peek >= hwm for m in masters),
               timeout=5, msg="hwm replicated")
    # let the short-TTL grant expire everywhere before the failover
    wait_until(lambda: all(m.fid_leases.active() == 0 for m in masters),
               timeout=5, msg="grant expired on all masters")

    leader.stop()
    new_leader = _wait_for_leader(_live(masters))
    # expired grants do not resurrect on the new leader...
    assert new_leader.fid_leases.active() == 0
    # ...and the expired range is still burned: next keys start past it
    assert new_leader.sequencer.peek >= hwm
    key = new_leader.sequencer.next_id(16)
    assert key >= hwm


def test_follower_serves_lookup_for_leased_volume(ha_cluster):
    """Once the leader's KeepConnected feed reaches a follower, the
    follower answers /dir/lookup for a leased volume itself (source
    'follower', leader hint in the body) instead of redirecting."""
    masters, vs, mc = ha_cluster
    leader = _wait_for_leader(masters)
    lease = mc.lease_fids(8)
    follower = next(m for m in masters if m is not leader)

    wait_until(lambda: follower._follower is not None
               and follower._follower.lookup(lease.vid) is not None,
               timeout=10, msg="follower cache learned the leased volume")
    locs, source = follower.lookup_locations(lease.vid)
    assert source == "follower"
    assert any(l["url"] == vs.url for l in locs)

    from seaweedfs_tpu.stats import MASTER_LOOKUP_COUNTER
    served = MASTER_LOOKUP_COUNTER.value("follower")
    r = requests.get(f"http://127.0.0.1:{follower.http_port}/dir/lookup",
                     params={"volumeId": str(lease.vid)}, timeout=5)
    assert r.status_code == 200
    assert MASTER_LOOKUP_COUNTER.value("follower") > served
    body = r.json()
    assert body.get("leader") == leader.address
    assert any(l["url"] == vs.url for l in body["locations"])
