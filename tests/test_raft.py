"""Raft master quorum: election, log replication, failover.

Reference: weed/server/raft_server.go (FSM = MaxVolumeId), leader gating
of Assign (master_grpc_server_assign.go:40), KeepConnected leader hints.
"""

import socket
import time

import pytest

from seaweedfs_tpu.master.master_server import MasterServer


def _fp():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_for_leader(masters, timeout=10.0):
    from conftest import wait_until
    out = []

    def one_leader():
        out[:] = [m for m in masters if m.is_leader and not m._stop.is_set()]
        return len(out) == 1

    wait_until(one_leader, timeout=timeout,
               msg=f"single leader among {[m.address for m in masters]}")
    return out[0]


@pytest.fixture()
def quorum(tmp_path):
    ports = [_fp() for _ in range(3)]
    peers = [f"127.0.0.1:{p}" for p in ports]
    masters = []
    for p in ports:
        ms = MasterServer(port=p, volume_size_limit_mb=64,
                          pulse_seconds=0.5, peers=peers,
                          raft_state_path=str(tmp_path / f"raft-{p}.json"))
        ms.start()
        masters.append(ms)
    yield masters
    for m in masters:
        m.stop()


class TestElection:
    def test_single_leader_elected(self, quorum):
        leader = _wait_for_leader(quorum)
        from conftest import wait_until
        wait_until(lambda: all(m.leader_address == leader.address
                               for m in quorum), msg="followers learn leader")

    def test_leader_failover(self, quorum):
        from seaweedfs_tpu.stats import RAFT_LEADER_CHANGES
        leader = _wait_for_leader(quorum)
        changes = RAFT_LEADER_CHANGES.value()
        leader.stop()
        rest = [m for m in quorum if m is not leader]
        new_leader = _wait_for_leader(rest)
        assert new_leader is not leader
        assert RAFT_LEADER_CHANGES.value() > changes

    def test_non_leader_rejects_assign(self, quorum):
        from seaweedfs_tpu.pb import master_pb2 as mpb

        leader = _wait_for_leader(quorum)
        from conftest import wait_until
        follower = next(m for m in quorum if m is not leader)
        wait_until(lambda: follower.leader_address == leader.address,
                   msg="follower learns leader")
        resp = follower.do_assign(mpb.AssignRequest(count=1))
        assert "not leader" in resp.error
        assert leader.address in resp.error

    def test_max_volume_id_replicated(self, quorum):
        leader = _wait_for_leader(quorum)
        ok = leader.raft.propose({"max_volume_id": 41})
        assert ok
        from conftest import wait_until
        wait_until(lambda: all(m.topo.max_volume_id >= 41 for m in quorum),
                   timeout=5, msg="max_volume_id replicated")

    def test_seq_hwm_replicated(self, quorum):
        """The sequencer high-water mark rides the raft log: every
        master's sequencer moves past committed fid ranges, so a new
        leader can never reissue keys an old leader acked."""
        leader = _wait_for_leader(quorum)
        assert leader.raft.propose({"seq_hwm": 500})
        from conftest import wait_until
        wait_until(lambda: all(m.sequencer.peek >= 500 for m in quorum),
                   timeout=5, msg="seq_hwm replicated")

    def test_lease_grant_replicated(self, quorum):
        """A fid-range lease grant committed by the leader lands in
        every master's registry (leases-active gauge correct wherever
        scraped / whoever becomes leader next)."""
        leader = _wait_for_leader(quorum)
        assert leader.raft.propose(
            {"seq_hwm": 4097, "lease": {"count": 4096, "ttl_s": 60.0}})
        from conftest import wait_until
        wait_until(lambda: all(m.fid_leases.active() == 1 for m in quorum),
                   timeout=5, msg="lease grant replicated")
        assert all(m.sequencer.peek >= 4097 for m in quorum)

    def test_admin_cron_notified_on_election(self, quorum):
        """The new leader's maintenance cron gets a resume notification
        (prompt first sweep on the production schedule); followers are
        never notified."""
        from conftest import wait_until
        leader = _wait_for_leader(quorum)
        wait_until(lambda: leader.admin_cron.resumes >= 1,
                   msg="leader cron notified")
        before = {m.address: m.admin_cron.resumes for m in quorum}
        leader.stop()
        rest = [m for m in quorum if m is not leader]
        new_leader = _wait_for_leader(rest)
        wait_until(lambda: new_leader.admin_cron.resumes
                   > before[new_leader.address],
                   msg="new leader cron resumed")

    def test_raft_state_persists(self, tmp_path):
        from seaweedfs_tpu.master.raft import LogEntry, RaftNode

        path = str(tmp_path / "raft.json")
        n = RaftNode("a:1", ["a:1", "b:2"], lambda c: None, state_path=path)
        n.current_term = 7
        n.voted_for = "b:2"
        n.log.append(LogEntry(7, {"max_volume_id": 3}))
        n._persist()
        n2 = RaftNode("a:1", ["a:1", "b:2"], lambda c: None, state_path=path)
        assert n2.current_term == 7
        assert n2.voted_for == "b:2"
        assert n2.log[0].command == {"max_volume_id": 3}


class TestVoteDurability:
    """Satellite: persisted vote/term state must be durable BEFORE the
    RPC reply leaves — including the rename's directory entry. A crash
    after replying 'granted' that resurrects the pre-vote state lets the
    node vote twice in one term (two leaders, split-brain)."""

    def test_vote_survives_crash_replay(self, tmp_path):
        from seaweedfs_tpu.master.raft import RaftNode

        path = str(tmp_path / "raft.json")
        members = ["a:1", "b:2", "c:3"]
        n = RaftNode("a:1", members, lambda c: None, state_path=path)
        out = n._on_request_vote({"term": 5, "candidate": "b:2",
                                  "last_log_index": -1, "last_log_term": 0})
        assert out["granted"]
        n.stop()
        # crash-replay: reconstruct from the same state path
        n2 = RaftNode("a:1", members, lambda c: None, state_path=path)
        assert n2.current_term == 5
        assert n2.voted_for == "b:2"
        # a competing candidate in the SAME term must be denied ...
        out = n2._on_request_vote({"term": 5, "candidate": "c:3",
                                   "last_log_index": 3, "last_log_term": 5})
        assert not out["granted"]
        # ... while the original candidate's retransmit is re-granted
        out = n2._on_request_vote({"term": 5, "candidate": "b:2",
                                   "last_log_index": -1, "last_log_term": 0})
        assert out["granted"]
        n2.stop()

    def test_term_adoption_survives_crash_replay(self, tmp_path):
        from seaweedfs_tpu.master.raft import RaftNode

        path = str(tmp_path / "raft.json")
        members = ["a:1", "b:2", "c:3"]
        n = RaftNode("a:1", members, lambda c: None, state_path=path)
        out = n._on_append_entries({"term": 9, "leader": "b:2",
                                    "prev_log_index": -1, "prev_log_term": 0,
                                    "entries": [], "snapshot": None,
                                    "leader_commit": -1})
        assert out["success"]
        n.stop()
        n2 = RaftNode("a:1", members, lambda c: None, state_path=path)
        # the adopted term was durable before the reply: after a crash
        # this node can never vote in a term below 9 again
        assert n2.current_term == 9
        out = n2._on_request_vote({"term": 8, "candidate": "c:3",
                                   "last_log_index": 99, "last_log_term": 8})
        assert not out["granted"]
        n2.stop()


class TestRedirectProtocol:
    """Satellite: typed leader redirects on the HTTP plane (421 +
    `leader` hint) and the follower lookup write barrier."""

    @pytest.fixture()
    def quorum_http(self, tmp_path):
        ports = [_fp() for _ in range(3)]
        peers = [f"127.0.0.1:{p}" for p in ports]
        masters = []
        for p in ports:
            ms = MasterServer(port=p, volume_size_limit_mb=64,
                              pulse_seconds=0.5, peers=peers,
                              http_port=_fp(),
                              raft_state_path=str(tmp_path / f"raft-{p}.json"))
            ms.start()
            masters.append(ms)
        yield masters
        for m in masters:
            m.stop()

    def test_follower_http_redirects(self, quorum_http):
        import requests

        from conftest import wait_until
        leader = _wait_for_leader(quorum_http)
        follower = next(m for m in quorum_http if m is not leader)
        wait_until(lambda: follower.leader_address == leader.address,
                   msg="follower learns leader")
        base = f"http://127.0.0.1:{follower.http_port}"
        # /cluster/status carries the lowercase `leader` hint
        st = requests.get(f"{base}/cluster/status", timeout=5).json()
        assert st["leader"] == leader.address
        assert st["IsLeader"] is False
        # mutating call on a follower: 421 + typed redirect body
        r = requests.get(f"{base}/dir/assign", params={"count": 1},
                         timeout=5)
        assert r.status_code == 421
        body = r.json()
        assert body["error"].startswith("not leader")
        assert body["leader"] == leader.address
        # lookup of an unknown vid on a follower: redirect, never an
        # authoritative 404 (the write barrier)
        r = requests.get(f"{base}/dir/lookup", params={"volumeId": "123"},
                         timeout=5)
        assert r.status_code == 421
        assert r.json()["leader"] == leader.address
        # the leader itself 404s authoritatively
        r = requests.get(
            f"http://127.0.0.1:{leader.http_port}/dir/lookup",
            params={"volumeId": "123"}, timeout=5)
        assert r.status_code == 404


class TestFailoverEndToEnd:
    def test_write_survives_leader_change(self, quorum, tmp_path):
        """Volume servers + clients follow the new leader and writes
        keep working after the old leader dies."""
        import requests

        from seaweedfs_tpu.client import operation
        from seaweedfs_tpu.client.master_client import MasterClient
        from seaweedfs_tpu.server.volume_server import VolumeServer
        from seaweedfs_tpu.storage.disk_location import DiskLocation
        from seaweedfs_tpu.storage.store import Store

        leader = _wait_for_leader(quorum)
        all_addrs = ",".join(m.address for m in quorum)
        vport = _fp()
        store = Store("127.0.0.1", vport, "",
                      [DiskLocation(str(tmp_path / "vols"),
                                    max_volume_count=8)],
                      coder_name="numpy")
        vs = VolumeServer(store, all_addrs, port=vport,
                          grpc_port=_fp(), pulse_seconds=0.3)
        vs.start()
        from conftest import wait_until

        def vs_up():
            try:
                return requests.get(f"http://{vs.url}/status", timeout=1).ok
            except Exception:
                return False

        wait_until(lambda: len(leader.topo.nodes) >= 1, msg="vs registered")
        wait_until(vs_up, msg="vs http up")
        mc = MasterClient(all_addrs).start()
        mc.wait_connected()
        try:
            r1 = operation.submit(mc, b"before failover", name="a")
            assert operation.read(mc, r1.fid) == b"before failover"

            leader.stop()
            survivors = [m for m in quorum if m is not leader]
            new_leader = _wait_for_leader(survivors)
            # volume server re-registers with the new leader via the
            # heartbeat leader hint
            wait_until(lambda: len(new_leader.topo.nodes) >= 1, timeout=15,
                       msg="vs re-registered with new leader")
            assert len(new_leader.topo.nodes) == 1

            deadline = time.time() + 15
            last = None
            while time.time() < deadline:
                try:
                    r2 = operation.submit(mc, b"after failover", name="b")
                    break
                except Exception as e:  # noqa: BLE001
                    last = e
                    time.sleep(0.3)
            else:
                raise AssertionError(f"write after failover: {last}")
            assert operation.read(mc, r2.fid) == b"after failover"
        finally:
            mc.stop()
            vs.stop()


def test_wal_persistence_and_torn_tail(tmp_path):
    """Appends hit an fsync'd WAL (O(1)/entry); restart replays it; a torn
    final line after a crash is dropped; the old single-JSON format still
    loads (migration)."""
    import json
    import os

    from seaweedfs_tpu.master.raft import LogEntry, RaftNode

    applied = []
    path = str(tmp_path / "raft.json")
    n = RaftNode("a:1", ["a:1"], applied.append, state_path=path)
    n.role = "leader"
    n.current_term = 3
    for i in range(5):
        n.log.append(LogEntry(3, {"max_volume_id": i + 1}))
        n._wal_append(n.log[-1:])
    n._persist_meta()
    n.stop()
    # wal = header (log_start) + one line per entry; meta has no inline log
    wal_lines = open(path + ".wal", "rb").read().splitlines()
    assert len(wal_lines) == 6
    assert json.loads(wal_lines[0]) == {"log_start": 0}
    assert "log" not in json.load(open(path))

    n2 = RaftNode("a:1", ["a:1"], applied.append, state_path=path)
    assert [e.command for e in n2.log][-1] == {"max_volume_id": 5}
    assert n2.current_term == 3
    n2.stop()

    # torn tail: truncate mid-line; replay keeps the whole records only
    with open(path + ".wal", "r+b") as f:
        f.truncate(os.path.getsize(path + ".wal") - 4)
    n3 = RaftNode("a:1", ["a:1"], applied.append, state_path=path)
    assert len(n3.log) == 4
    n3.stop()

    # crash between WAL rewrite and metadata rewrite: the WAL header's
    # log_start overrides stale metadata so entry indices stay aligned
    import copy
    meta = json.load(open(path))
    n5 = RaftNode("a:1", ["a:1"], applied.append, state_path=path)
    n5.log_start = 3
    n5.log = n5.log[3:]
    tmp = path + ".wal.tmp"
    with open(tmp, "wb") as f:  # simulate: WAL rewritten, meta NOT
        f.write(json.dumps({"log_start": 3}).encode() + b"\n")
        for e in n5.log:
            f.write(json.dumps({"t": e.term, "c": e.command}).encode()
                    + b"\n")
    os.replace(tmp, path + ".wal")
    n5.stop()
    json.dump(meta, open(path, "w"))  # stale meta still says log_start=0
    n6 = RaftNode("a:1", ["a:1"], applied.append, state_path=path)
    assert n6.log_start == 3  # WAL header won
    assert len(n6.log) == 1
    n6.stop()

    # legacy format: inline log in the json, no wal
    legacy = str(tmp_path / "legacy.json")
    json.dump({"term": 7, "voted_for": None, "log_start": 0,
               "snapshot_state": {}, "snapshot_term": 0,
               "log": [{"term": 7, "command": {"max_volume_id": 9}}]},
              open(legacy, "w"))
    n4 = RaftNode("a:1", ["a:1"], applied.append, state_path=legacy)
    assert n4.current_term == 7
    assert n4.log[0].command == {"max_volume_id": 9}
    n4.stop()


class TestMembership:
    """cluster.raft.add / cluster.raft.remove (reference
    command_cluster_raft_add.go, command_cluster_raft_remove.go,
    master RaftAddServer/RaftRemoveServer RPCs)."""

    def test_add_server_learns_and_replicates(self, quorum, tmp_path):
        leader = _wait_for_leader(quorum)
        newport = _fp()
        addr = f"127.0.0.1:{newport}"
        # the joiner seeds only itself + one existing member; the config
        # entry in the replicated log teaches it the real membership
        joiner = MasterServer(port=newport, volume_size_limit_mb=64,
                              peers=[addr, leader.address],
                              raft_state_path=str(tmp_path / "raft-new.json"))
        joiner.start()
        try:
            assert leader.raft.add_server(addr)
            from conftest import wait_until
            wait_until(lambda: set(joiner.raft.cluster_members)
                       == set(leader.raft.cluster_members)
                       and len(leader.raft.cluster_members) == 4,
                       msg="membership replicated to joiner")
            assert len(leader.raft.cluster_members) == 4
            assert set(joiner.raft.cluster_members) == \
                set(leader.raft.cluster_members)
            # state replicates to the joiner
            assert leader.raft.propose({"max_volume_id": 77})
            wait_until(lambda: joiner.topo.max_volume_id >= 77, timeout=5,
                       msg="state replicated to joiner")
        finally:
            joiner.stop()

    def test_remove_follower_quiesces_it(self, quorum):
        leader = _wait_for_leader(quorum)
        from conftest import wait_until
        wait_until(lambda: all(m.leader_address == leader.address
                               for m in quorum), msg="quorum settled")
        victim = next(m for m in quorum if m is not leader)
        assert leader.raft.remove_server(victim.address)
        assert victim.address not in leader.raft.cluster_members
        # remaining pair still commits (quorum of 2)
        assert leader.raft.propose({"max_volume_id": 99})
        # the victim learns of its removal via the courtesy append and
        # stops campaigning instead of disrupting the survivors
        wait_until(lambda: not victim.raft.peers, timeout=5,
                   msg="victim learns removal")
        assert victim.raft.peers == []
        # survivors refuse votes to the removed node (no term bumps)
        term_before = leader.raft.current_term
        time.sleep(1.2)   # long enough for the victim to have campaigned
        assert _wait_for_leader([m for m in quorum if m is not victim]) \
            is leader
        assert leader.raft.current_term == term_before
