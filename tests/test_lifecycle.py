"""Tiered-storage lifecycle plane (seaweedfs_tpu/lifecycle/).

Policy parsing, the pure planner over synthetic topologies + heat
reports, the budgeted executor (dry-run zero-dispatch, byte budget,
cooldown, locks), the storage tiering primitives (EC shard offload /
promote / DestroyTime reap boundary / trash restore), and the
end-to-end plane on a mini-cluster: lifecycle.apply walks a cooling
collection hot → EC → remote and promotes it back on heat, with
/debug/lifecycle serving the heat reports that drive it.
"""

import io
import json
import os
import socket
import threading
import time
from types import SimpleNamespace

import pytest

from seaweedfs_tpu.lifecycle import (LifecycleExecutor, TIER_EC, TIER_HOT,
                                     TIER_REMOTE, build_lifecycle_plan,
                                     parse_policy)
from seaweedfs_tpu.lifecycle.planner import (KIND_ENCODE, KIND_OFFLOAD,
                                             KIND_PROMOTE, KIND_STAMP,
                                             LifecyclePlan, Transition)
from seaweedfs_tpu.shell import (lifecycle_commands,  # noqa: F401 (register)
                                 volume_commands)
from seaweedfs_tpu.shell import ec_commands  # noqa: F401 (register)
from seaweedfs_tpu.storage.disk_location import DiskLocation
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import Store


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


# -- policy ------------------------------------------------------------------

class TestPolicy:
    def test_parse_and_match_order(self):
        pol = parse_policy({"rules": [
            {"collection": "logs", "ec_after_s": 10},
            {"collection": "*", "ec_after_s": 100,
             "remote_after_s": 200, "remote": "local:/tmp/x",
             "promote_reads": 4, "ttl_s": 300}]})
        assert pol.rule_for("logs").ec_after_s == 10
        assert pol.rule_for("logs").remote_after_s is None
        assert pol.rule_for("anything").ec_after_s == 100
        assert pol.rule_for("").promote_reads == 4
        # round-trips through the doc form (master /debug/lifecycle)
        assert parse_policy(pol.to_doc()).rule_for("logs").ec_after_s == 10

    def test_parse_rejects_bad_rules(self):
        with pytest.raises(ValueError, match="remote"):
            parse_policy({"rules": [{"remote_after_s": 5}]})
        with pytest.raises(ValueError, match="unknown keys"):
            parse_policy({"rules": [{"ec_after": 5}]})
        with pytest.raises(ValueError, match=">= 0"):
            parse_policy({"rules": [{"ec_after_s": -1}]})

    def test_parse_from_file(self, tmp_path):
        p = tmp_path / "pol.json"
        p.write_text(json.dumps({"rules": [{"ec_after_s": 7}]}))
        pol = parse_policy(str(p))
        assert pol.rule_for("x").ec_after_s == 7
        assert pol.source == str(p)


# -- planner (synthetic topology + heat, zero RPCs) --------------------------

def _srv(sid, vols=(), ecs=()):
    """A collect_volume_servers()-shaped server: vols = (vid, col,
    size), ecs = (vid, col)."""
    return {"id": sid, "grpc_port": 10000,
            "disks": {"hdd": SimpleNamespace(
                volume_infos=[SimpleNamespace(id=v, collection=c, size=s)
                              for v, c, s in vols],
                ec_shard_infos=[SimpleNamespace(id=v, collection=c)
                                for v, c in ecs])}}


def _env(servers):
    return SimpleNamespace(collect_volume_servers=lambda: servers)


def _vol_heat(write_age, read_age=None, tiered=False, size=1000):
    return {"last_write_age_s": write_age, "last_read_age_s": read_age,
            "tiered": tiered, "size": size, "reads": 0}


def _ec_heat(local=(), remote=(), read_age=0.0, remote_reads=0,
             destroy_time=0, shard_size=100):
    return {"local_shards": list(local), "remote_shards": list(remote),
            "last_read_age_s": read_age, "remote_reads": remote_reads,
            "destroy_time": destroy_time, "shard_size": shard_size,
            "reads": 0}


POL = parse_policy({"rules": [{"collection": "*", "ec_after_s": 60,
                               "remote_after_s": 600,
                               "remote": "local:/tmp/r",
                               "promote_reads": 3,
                               "min_size_bytes": 100}]})


class TestPlanner:
    def test_encode_planned_when_quiet(self):
        srv = _srv("a:1", vols=[(1, "c", 5000)])
        heat = {"a:1": {"volumes": {"1": _vol_heat(120, 300)},
                        "ec_volumes": {}}}
        plan = build_lifecycle_plan(_env([srv]), POL, heat=heat)
        assert [t.kind for t in plan.transitions] == [KIND_ENCODE]
        t = plan.transitions[0]
        assert (t.vid, t.from_tier, t.to_tier) == (1, TIER_HOT, TIER_EC)
        assert t.bytes_est == 5000

    def test_encode_blocked_by_recent_activity(self):
        srv = _srv("a:1", vols=[(1, "c", 5000), (2, "c", 5000)])
        heat = {"a:1": {"volumes": {"1": _vol_heat(10, None),
                                    "2": _vol_heat(120, 5)},
                        "ec_volumes": {}}}
        plan = build_lifecycle_plan(_env([srv]), POL, heat=heat)
        assert plan.transitions == []  # 1: recent write; 2: recent read

    def test_unrecorded_reads_bounded_by_uptime(self):
        """Read counters are in-memory: after a restart a read-hot but
        write-quiet volume reports last_read_age_s=None — the planner
        must bound that by the server's uptime, not treat it as
        never-read and encode a volume that is actively served."""
        srv = _srv("a:1", vols=[(1, "c", 5000)])
        heat = {"a:1": {"uptime_s": 10.0,  # just restarted
                        "volumes": {"1": _vol_heat(120, None)},
                        "ec_volumes": {}}}
        plan = build_lifecycle_plan(_env([srv]), POL, heat=heat)
        assert plan.transitions == []  # quiet attested only 10s < 60s
        heat["a:1"]["uptime_s"] = 90.0
        plan = build_lifecycle_plan(_env([srv]), POL, heat=heat)
        assert [t.kind for t in plan.transitions] == [KIND_ENCODE]

    def test_missing_heat_vetoes(self):
        srvs = [_srv("a:1", vols=[(1, "c", 5000)]),
                _srv("b:1", vols=[(1, "c", 5000)])]
        heat = {"a:1": {"volumes": {"1": _vol_heat(120)},
                        "ec_volumes": {}}}  # b:1 unreachable
        plan = build_lifecycle_plan(_env(srvs), POL, heat=heat)
        assert plan.transitions == [] and plan.skipped_no_heat == [1]

    def test_min_size_and_tiered_skip(self):
        srv = _srv("a:1", vols=[(1, "c", 10), (2, "c", 5000)])
        heat = {"a:1": {"volumes": {"1": _vol_heat(120),
                                    "2": _vol_heat(120, tiered=True)},
                        "ec_volumes": {}}}
        plan = build_lifecycle_plan(_env([srv]), POL, heat=heat)
        assert plan.transitions == [] and 2 in plan.skipped_no_heat

    def test_offload_needs_every_holder_cold(self):
        srvs = [_srv("a:1", ecs=[(3, "c")]), _srv("b:1", ecs=[(3, "c")])]
        heat = {"a:1": {"volumes": {},
                        "ec_volumes": {"3": _ec_heat(local=[0, 1],
                                                     read_age=700)}},
                "b:1": {"volumes": {},
                        "ec_volumes": {"3": _ec_heat(local=[2],
                                                     read_age=30)}}}
        plan = build_lifecycle_plan(_env(srvs), POL, heat=heat)
        assert plan.transitions == []  # b:1 saw a read 30 s ago
        heat["b:1"]["ec_volumes"]["3"]["last_read_age_s"] = 700
        plan = build_lifecycle_plan(_env(srvs), POL, heat=heat)
        assert [t.kind for t in plan.transitions] == [KIND_OFFLOAD]
        t = plan.transitions[0]
        assert t.bytes_est == 3 * 100 and len(t.servers) == 2
        assert t.remote == "local:/tmp/r"

    def test_promote_beats_offload_and_orders_first(self):
        srvs = [_srv("a:1", vols=[(1, "c", 50_000)],
                     ecs=[(3, "c"), (4, "c")])]
        heat = {"a:1": {"volumes": {"1": _vol_heat(120)},
                        "ec_volumes": {
                            # 3 is offloaded AND hot: promote, not
                            # re-offload, even though read_age is huge
                            "3": _ec_heat(remote=[0, 1, 2],
                                          read_age=9999, remote_reads=5),
                            "4": _ec_heat(local=[0, 1], read_age=9999)}}}
        plan = build_lifecycle_plan(_env(srvs), POL, heat=heat)
        assert [t.kind for t in plan.transitions] == [
            KIND_PROMOTE, KIND_ENCODE, KIND_OFFLOAD]
        assert plan.transitions[0].vid == 3
        assert plan.transitions[0].from_tier == TIER_REMOTE

    def test_pending_reaps_reported(self):
        srvs = [_srv("a:1", ecs=[(3, "c")])]
        heat = {"a:1": {"volumes": {},
                        "ec_volumes": {"3": _ec_heat(
                            local=[0], destroy_time=1000.0)}}}
        plan = build_lifecycle_plan(_env(srvs), POL, heat=heat,
                                    now=900.0)
        assert plan.pending_reaps == [{
            "vid": 3, "collection": "c", "due_in_s": 100.0}]

    def test_multi_disk_server_counted_once(self):
        """A server holding a stripe across TWO of its disks is one
        holder: heat must not double, bytes must not double, and the
        executor must not RPC it twice."""
        srv = _srv("a:1", ecs=[(3, "c")])
        srv["disks"]["ssd"] = SimpleNamespace(
            volume_infos=[],
            ec_shard_infos=[SimpleNamespace(id=3, collection="c")])
        heat = {"a:1": {"volumes": {},
                        "ec_volumes": {"3": _ec_heat(
                            remote=[0, 1], read_age=9999,
                            remote_reads=2)}}}
        # remote_reads=2 < promote_reads=3: a double-counted holder
        # (2+2=4) would promote here — it must NOT
        plan = build_lifecycle_plan(_env([srv]), POL, heat=heat)
        assert all(t.kind != KIND_PROMOTE for t in plan.transitions)
        heat["a:1"]["ec_volumes"]["3"]["remote_reads"] = 3
        plan = build_lifecycle_plan(_env([srv]), POL, heat=heat)
        promote = [t for t in plan.transitions if t.kind == KIND_PROMOTE]
        assert len(promote) == 1 and len(promote[0].servers) == 1
        assert promote[0].bytes_est == 2 * 100  # not 4 * 100

    def test_ttl_rule_stamps_until_destroy_time_set(self):
        """An EC volume under a ttl rule that lacks a DestroyTime gets
        a stamp transition EVERY sweep — the retry path for a stamp
        that failed right after the irreversible encode — and stops
        being planned once the holders report one."""
        pol = parse_policy({"rules": [{"collection": "*",
                                       "ttl_s": 300}]})
        srvs = [_srv("a:1", ecs=[(3, "c")])]
        heat = {"a:1": {"volumes": {},
                        "ec_volumes": {"3": _ec_heat(local=[0, 1])}}}
        plan = build_lifecycle_plan(_env(srvs), pol, heat=heat)
        assert [t.kind for t in plan.transitions] == [KIND_STAMP]
        t = plan.transitions[0]
        assert t.ttl_s == 300 and t.bytes_est == 0 and t.servers
        # stamped: no more stamp transitions, a pending reap instead
        heat["a:1"]["ec_volumes"]["3"]["destroy_time"] = 5000.0
        plan = build_lifecycle_plan(_env(srvs), pol, heat=heat,
                                    now=4000.0)
        assert plan.transitions == []
        assert [r["vid"] for r in plan.pending_reaps] == [3]


# -- executor ----------------------------------------------------------------

def _plan(*transitions):
    p = LifecyclePlan()
    p.transitions.extend(transitions)
    return p


def _tr(vid, kind=KIND_OFFLOAD, nbytes=100):
    return Transition(kind, vid, "c", nbytes, reason="test")


class TestExecutor:
    def _exec(self, **kw):
        ex = LifecycleExecutor(_env([]), **kw)
        ran = []
        ex._dispatch = lambda t: ran.append(t.vid) or t.bytes_est
        return ex, ran

    def test_dry_run_dispatches_nothing_but_journals_plan(self):
        from seaweedfs_tpu.ops import events
        ex, ran = self._exec()
        before = events.JOURNAL.last_seq
        res = ex.execute(_plan(_tr(1), _tr(2)), dry_run=True)
        assert ran == [] and res == {"done": [], "failed": [],
                                     "skipped": []}
        evs = events.JOURNAL.snapshot(since=before, etype="lifecycle.plan")
        assert evs and evs[-1]["attrs"]["dry_run"] is True
        assert evs[-1]["attrs"]["transitions"] == 2

    def test_byte_budget_cheapest_first(self):
        ex, ran = self._exec(max_bytes=35, max_concurrent=1)
        res = ex.execute(_plan(_tr(1, nbytes=10), _tr(2, nbytes=20),
                               _tr(3, nbytes=1000)))
        assert sorted(ran) == [1, 2]
        assert [s["reason"] for s in res["skipped"]] == ["budget"]

    def test_oversized_single_transition_passes_untouched_budget(self):
        ex, ran = self._exec(max_bytes=35)
        res = ex.execute(_plan(_tr(9, nbytes=10_000), _tr(1, nbytes=10)))
        assert ran == [9]  # admitted against an untouched budget
        assert [s["vid"] for s in res["skipped"]] == [1]

    def test_transition_count_budget(self):
        ex, ran = self._exec(max_transitions=1)
        res = ex.execute(_plan(_tr(1), _tr(2)))
        assert len(ran) == 1 and len(res["skipped"]) == 1

    def test_stamp_without_holders_fails_for_retry(self):
        """An empty holder list (heartbeat lag right after encode) must
        FAIL the stamp transition — never silently no-op — so cooldown
        + the next sweep's re-plan retry it."""
        ex = LifecycleExecutor(_env([]))
        res = ex.execute(_plan(Transition(KIND_STAMP, 9, "c", 0,
                                          reason="t", ttl_s=60)))
        assert [f["vid"] for f in res["failed"]] == [9]
        assert "no registered holders" in res["failed"][0]["error"]

    def test_failure_cooldown_with_backoff(self):
        ex = LifecycleExecutor(_env([]), cooldown_s=30.0)
        calls = []

        def boom(t):
            calls.append(t.vid)
            raise RuntimeError("remote tier down")

        ex._dispatch = boom
        res = ex.execute(_plan(_tr(5)))
        assert [f["vid"] for f in res["failed"]] == [5]
        res2 = ex.execute(_plan(_tr(5)))
        assert [s["reason"] for s in res2["skipped"]] == ["cooldown"]
        assert calls == [5]  # the cooling volume was not retried

    def test_success_moves_metrics_and_journals(self):
        from seaweedfs_tpu.ops import events
        from seaweedfs_tpu.stats import (LIFECYCLE_BYTES_MOVED,
                                         LIFECYCLE_TRANSITIONS)
        from seaweedfs_tpu import qos
        ex, ran = self._exec()
        classes = []
        count = ex._dispatch
        ex._dispatch = lambda t: classes.append(qos.current_class()) \
            or count(t)
        before_n = LIFECYCLE_TRANSITIONS.value(TIER_EC, TIER_REMOTE)
        before_b = LIFECYCLE_BYTES_MOVED.value(TIER_EC, TIER_REMOTE)
        seq = events.JOURNAL.last_seq
        ex.execute(_plan(_tr(7, nbytes=123)))
        # the move's reads and uploads admit behind foreground tenants
        assert classes == [qos.CLASS_MAINTENANCE]
        assert LIFECYCLE_TRANSITIONS.value(TIER_EC, TIER_REMOTE) \
            == before_n + 1
        assert LIFECYCLE_BYTES_MOVED.value(TIER_EC, TIER_REMOTE) \
            == before_b + 123
        evs = events.JOURNAL.snapshot(since=seq,
                                      etype="lifecycle.transition")
        at = evs[-1]["attrs"] if evs else {}
        assert at.get("vid") == 7 and at.get("from") == TIER_EC \
            and at.get("to") == TIER_REMOTE


# -- storage tiering primitives (no cluster) ---------------------------------

@pytest.fixture
def ec_store(tmp_path):
    d = tmp_path / "vols"
    d.mkdir()
    store = Store("127.0.0.1", 0, "",
                  [DiskLocation(str(d), max_volume_count=8)],
                  coder_name="numpy")
    v = store.add_volume(5, collection="cool")
    payloads = {}
    for i in range(1, 20):
        data = os.urandom(2000 + i)
        v.write_needle(Needle(id=i, cookie=7, data=data))
        payloads[i] = data
    v.sync()
    store.generate_ec_shards(5, collection="cool")
    store.delete_volume(5)
    store.mount_ec_shards(5, "cool")
    yield store, payloads, str(tmp_path / "remote"), str(d)
    store.close()


class TestStorageTiering:
    def test_offload_reads_promote_roundtrip(self, ec_store):
        store, payloads, remote, vol_dir = ec_store
        spec = f"local:{remote}"
        moved = store.offload_ec_shards(5, spec, collection="cool")
        assert moved > 0
        ev = store.find_ec_volume(5)
        assert ev.remote_shard_ids() == sorted(ev.shards)
        assert not any(f.endswith(".ec00")
                       for f in os.listdir(vol_dir))
        # the .vif survives and a restart reloads the remote mapping
        for i, data in payloads.items():
            assert store.read_needle(5, i, cookie=7).data == data
        assert ev.remote_reads() > 0
        # idempotent: nothing local left to move
        assert store.offload_ec_shards(5, spec, collection="cool") == 0
        # a second spec is refused (one remote tier per volume)
        with pytest.raises(ValueError, match="already offloaded"):
            store.offload_ec_shards(5, "local:/tmp/other",
                                    collection="cool")
        back = store.promote_ec_shards(5, collection="cool")
        assert back == moved
        assert store.find_ec_volume(5).remote_shard_ids() == []
        for i, data in payloads.items():
            assert store.read_needle(5, i, cookie=7).data == data
        from seaweedfs_tpu.storage.backend import LocalDirRemote
        assert LocalDirRemote(remote).list_keys() == []

    def test_offloaded_volume_survives_remount(self, ec_store):
        store, payloads, remote, _ = ec_store
        store.offload_ec_shards(5, f"local:{remote}", collection="cool")
        ev = store.mount_ec_shards(5, "cool")  # remount rescans disk+vif
        assert ev.remote_shard_ids() == sorted(ev.shards)
        assert store.read_needle(5, 3, cookie=7).data == payloads[3]

    def test_destroy_time_boundary_trash_and_restore(self, ec_store):
        store, payloads, _remote, vol_dir = ec_store
        from seaweedfs_tpu.ec import files as ec_files
        ev = store.find_ec_volume(5)
        vif = ec_files.read_vif(ev.base + ".vif")
        vif["destroy_time"] = 1000.0
        ec_files.write_vif(ev.base + ".vif", **vif)
        ev.destroy_time = 1000.0
        # strictly before the instant: NOT reaped
        assert store.delete_expired_ec_volumes(now=999.999) == []
        assert store.find_ec_volume(5) is not None
        # AT the instant: reaped into the soft-delete trash
        recs = store.delete_expired_ec_volumes(now=1000.0)
        assert [r["vid"] for r in recs] == [5]
        assert recs[0]["from"] == TIER_EC and recs[0]["bytes"] > 0
        assert store.find_ec_volume(5) is None
        trash = os.path.join(vol_dir, ".trash")
        assert any(f.endswith(".vif") for f in os.listdir(trash))
        # restorable before the trash grace expires
        store.restore_ec_volume_from_trash(5, "cool")
        for i, data in payloads.items():
            assert store.read_needle(5, i, cookie=7).data == data

    def test_promote_cleans_dual_copy_remote_objects(self, ec_store):
        """A shard present BOTH locally and remotely (a promote raced a
        crash) serves local — but its remote object must still be
        deleted when the promote pops the mapping, or it is orphaned
        forever."""
        store, payloads, remote, _ = ec_store
        from seaweedfs_tpu.storage.backend import LocalDirRemote
        store.offload_ec_shards(5, f"local:{remote}", collection="cool")
        ev = store.find_ec_volume(5)
        # simulate the crash state: shard 0's payload back on disk,
        # its key still in the mapping
        client = LocalDirRemote(remote)
        sid0 = ev.remote_shard_ids()[0]
        key0 = ev.remote_spec["keys"][str(sid0)]
        from seaweedfs_tpu.ec import files as ec_files
        client.read_object_to(key0, ev.base + ec_files.shard_ext(sid0))
        ev2 = store.mount_ec_shards(5, "cool")
        assert sid0 not in ev2.remote_shard_ids()  # local wins
        store.promote_ec_shards(5, collection="cool")
        assert client.list_keys() == []  # dual-copy object deleted too
        for i, data in payloads.items():
            assert store.read_needle(5, i, cookie=7).data == data

    def test_vif_updates_are_atomic_and_locked(self, ec_store):
        """Concurrent .vif writers (idle stamp, tier seal, DestroyTime)
        must never lose each other's keys, and a write never leaves a
        truncated sidecar."""
        store, _payloads, _remote, _ = ec_store
        from seaweedfs_tpu.ec import files as ec_files
        path = store.find_ec_volume(5).base + ".vif"
        stop = threading.Event()
        errs = []

        def writer(key):
            n = 0
            while not stop.is_set():
                n += 1
                try:
                    ec_files.update_vif(path, {key: n})
                    # every read must parse (atomic replace) and keep
                    # the structural keys (locked merge)
                    info = ec_files.read_vif(path)
                    if "shards" not in info and "d" not in info \
                            and "dat_size" not in info:
                        errs.append(f"structure lost: {sorted(info)}")
                        return
                except Exception as e:  # noqa: BLE001
                    errs.append(f"{key}: {e}")
                    return

        ts = [threading.Thread(target=writer, args=(k,))
              for k in ("last_read_wall", "destroy_time", "probe")]
        for t in ts:
            t.start()
        time.sleep(0.6)
        stop.set()
        for t in ts:
            t.join(timeout=10)
        assert not errs, errs[:3]
        info = ec_files.read_vif(path)
        assert {"last_read_wall", "destroy_time", "probe"} <= set(info)

    def test_resumed_reads_clear_stale_stamp(self, ec_store):
        """A persisted last-read stamp goes STALE the moment reads
        resume; the next housekeeping tick must clear it, or a restart
        would make a hot volume read as cold-for-days."""
        store, _payloads, _remote, _ = ec_store
        from seaweedfs_tpu.ec import files as ec_files
        ev = store.find_ec_volume(5)
        ev.last_read_at = time.monotonic() - 500
        assert ev.close_idle(idle_s=100.0)  # stamps
        assert "last_read_wall" in ec_files.read_vif(ev.base + ".vif")
        store.read_needle(5, 3, cookie=7)  # reads resume
        assert not ev.close_idle(idle_s=100.0)  # not idle: clears
        assert "last_read_wall" not in ec_files.read_vif(
            ev.base + ".vif")
        assert ev._last_read_wall == 0.0

    def test_idle_close_stamps_read_age_across_remount(self, ec_store):
        """The idle-close persists the last-read instant into the .vif
        so a remount (restart) does not reset the EC read-age clock to
        zero and postpone the EC→remote offload by a full
        remote_after_s."""
        store, _payloads, _remote, _ = ec_store
        ev = store.find_ec_volume(5)
        store.read_needle(5, 3, cookie=7)
        ev.last_read_at = time.monotonic() - 500  # idle for 500 s
        assert ev.close_idle(idle_s=100.0)
        ev2 = store.mount_ec_shards(5, "cool")  # "restart"
        # a fresh mount with no reads: age comes from the stamp, not
        # the mount instant
        assert ev2.read_age_s() >= 499.0

    def test_idle_close_racing_reads_never_fails(self, ec_store):
        """Fork behavior: idle EC handles close and reads lazily
        reopen — a close racing a concurrent reader must not fail the
        read (the shard mutex serializes close vs pread)."""
        store, payloads, _remote, _ = ec_store
        ev = store.find_ec_volume(5)
        stop = threading.Event()
        errors = []

        def reader(seed):
            i = seed
            while not stop.is_set():
                i = (i % 19) + 1
                try:
                    n = ev.read_needle(i, cookie=7)
                    if n.data != payloads[i]:
                        errors.append(f"bytes differ for {i}")
                        return
                except Exception as e:  # noqa: BLE001
                    errors.append(f"read {i}: {e}")
                    return

        ts = [threading.Thread(target=reader, args=(s,))
              for s in range(3)]
        for t in ts:
            t.start()
        deadline = time.monotonic() + 1.5
        closed = 0
        while time.monotonic() < deadline:
            ev.last_read_at = time.monotonic() - 10  # force idle
            if ev.close_idle(idle_s=1.0):
                closed += 1
        stop.set()
        for t in ts:
            t.join(timeout=10)
        assert not errors, errors
        assert closed > 0  # the race was actually exercised


def test_move_volume_local_never_unmaps_under_reads(tmp_path):
    """The same-server tier move maps the destination BEFORE unmapping
    the source (both frozen, identical bytes): a racing read must never
    see the vid unmapped mid-move."""
    d_hdd = tmp_path / "hdd"
    d_ssd = tmp_path / "ssd"
    d_hdd.mkdir()
    d_ssd.mkdir()
    store = Store("127.0.0.1", 0, "",
                  [DiskLocation(str(d_hdd), disk_type="hdd",
                                max_volume_count=4),
                   DiskLocation(str(d_ssd), disk_type="ssd",
                                max_volume_count=4)],
                  coder_name="numpy")
    v = store.add_volume(3)
    payloads = {}
    for i in range(1, 30):
        data = os.urandom(4000)
        v.write_needle(Needle(id=i, cookie=9, data=data))
        payloads[i] = data
    stop = threading.Event()
    errors = []

    def reader(seed):
        i = seed
        while not stop.is_set():
            i = (i % 29) + 1
            try:
                n = store.read_needle(3, i, cookie=9)
                if n.data != payloads[i]:
                    errors.append(f"wrong bytes for {i}")
                    return
            except Exception as e:  # noqa: BLE001
                errors.append(f"read {i}: {type(e).__name__}: {e}")
                return

    ts = [threading.Thread(target=reader, args=(s,)) for s in range(3)]
    for t in ts:
        t.start()
    try:
        for _ in range(4):  # bounce between tiers under the readers
            store.move_volume_local(3, "ssd")
            store.move_volume_local(3, "hdd")
    finally:
        stop.set()
        for t in ts:
            t.join(timeout=20)
    assert not errors, errors[:3]
    nv = store.find_volume(3)
    assert nv is not None and not nv.read_only  # freeze thawed
    store.close()


# -- same-server cross-tier move ---------------------------------------------

def test_same_server_tier_move(tmp_path):
    """volume.tier.move on a server that has BOTH disk types: a local
    disk-to-disk copy through VolumeCopy's same-server path (the old
    code refused: 'VolumeCopy rejects same-server')."""
    from conftest import wait_until
    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.master_client import MasterClient
    from seaweedfs_tpu.master.master_server import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.commands import CommandEnv, run_command

    mport = free_port()
    master = MasterServer(port=mport, volume_size_limit_mb=64,
                          pulse_seconds=0.3)
    master.start()
    hdd = tmp_path / "hdd"
    ssd = tmp_path / "ssd"
    hdd.mkdir()
    ssd.mkdir()
    port = free_port()
    store = Store("127.0.0.1", port, "",
                  [DiskLocation(str(hdd), disk_type="hdd",
                                max_volume_count=8),
                   DiskLocation(str(ssd), disk_type="ssd",
                                max_volume_count=8)],
                  coder_name="numpy")
    vs = VolumeServer(store, f"127.0.0.1:{mport}", port=port,
                      grpc_port=free_port(), pulse_seconds=0.3)
    vs.start()
    mc = None
    try:
        wait_until(lambda: len(master.topo.nodes) >= 1,
                   msg="server registered")
        mc = MasterClient(f"127.0.0.1:{mport}").start()
        res = operation.submit(mc, b"move me locally")
        vid = int(res.fid.split(",")[0])
        hdd_loc, ssd_loc = store.locations
        assert vid in hdd_loc.volumes and vid not in ssd_loc.volumes
        env = CommandEnv(f"127.0.0.1:{mport}", mc=mc, out=io.StringIO())
        env.acquire_lock()
        run_command(env, "volume.tier.move -fromDiskType hdd "
                         "-toDiskType ssd")
        env.release_lock()
        assert vid in ssd_loc.volumes and vid not in hdd_loc.volumes
        assert not os.path.exists(
            os.path.join(str(hdd), f"{vid}.dat"))
        assert operation.read(mc, res.fid) == b"move me locally"
        # still writable after the move (the freeze was thawed)
        res2 = operation.submit(mc, b"second write")
        assert operation.read(mc, res2.fid) == b"second write"
    finally:
        if mc is not None:
            mc.stop()
        vs.stop()
        master.stop()


# -- the whole plane on a mini-cluster ---------------------------------------

@pytest.fixture(scope="class")
def lifecycle_cluster(tmp_path_factory):
    from conftest import wait_cluster_up
    from seaweedfs_tpu.client.master_client import MasterClient
    from seaweedfs_tpu.ec.locate import EcGeometry
    from seaweedfs_tpu.master.master_server import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.commands import CommandEnv

    mport, mhttp = free_port(), free_port()
    master = MasterServer(port=mport, http_port=mhttp,
                          volume_size_limit_mb=64, pulse_seconds=0.3,
                          maintenance_scripts=[], ec_parity_shards=2)
    master.start()
    d = tmp_path_factory.mktemp("lcvols")
    port = free_port()
    store = Store("127.0.0.1", port, "",
                  [DiskLocation(str(d), max_volume_count=16)],
                  ec_geometry=EcGeometry(d=4, p=2, large_block=1 << 20,
                                         small_block=1 << 14),
                  coder_name="numpy")
    vs = VolumeServer(store, f"127.0.0.1:{mport}", port=port,
                      grpc_port=free_port(), pulse_seconds=0.3)
    vs.start()
    wait_cluster_up(master, [vs])
    mc = MasterClient(f"127.0.0.1:{mport}").start()
    out = io.StringIO()
    env = CommandEnv(f"127.0.0.1:{mport}", mc=mc, out=out)
    remote = str(tmp_path_factory.mktemp("lcremote"))
    pol_path = os.path.join(str(tmp_path_factory.mktemp("lcpol")),
                            "policy.json")
    with open(pol_path, "w", encoding="utf-8") as f:
        json.dump({"rules": [{"collection": "cool", "ec_after_s": 0,
                              "remote_after_s": 0,
                              "remote": f"local:{remote}",
                              "promote_reads": 3}]}, f)
    yield {"master": master, "vs": vs, "mc": mc, "env": env, "out": out,
           "remote": remote, "policy": pol_path, "mhttp": mhttp}
    mc.stop()
    vs.stop()
    master.stop()


def _sh(c, line):
    from seaweedfs_tpu.shell.commands import run_command
    c["out"].truncate(0)
    c["out"].seek(0)
    run_command(c["env"], line)
    return c["out"].getvalue()


class TestLifecycleCluster:
    def test_full_plane(self, lifecycle_cluster):
        from conftest import wait_until
        from seaweedfs_tpu.client import http_util, operation
        c = lifecycle_cluster
        master, vs, mc = c["master"], c["vs"], c["mc"]
        payloads = {}
        for i in range(18):
            data = os.urandom(1500 + 17 * i)
            r = operation.submit(mc, data, collection="cool")
            payloads[r.fid] = data
        vid = int(next(iter(payloads)).split(",")[0])
        wait_until(lambda: master.topo.lookup(vid), msg="vol registered")
        # topology must report a nonzero size before the planner will
        # cost the encode
        wait_until(lambda: any(v.size for n in master.topo.all_nodes()
                               for v in n.all_volumes()),
                   msg="size heartbeat")
        _sh(c, "lock")

        # -- dry run: full plan, ZERO mutating RPCs ----------------------
        text = _sh(c, f"lifecycle.apply -policy {c['policy']} -dryRun")
        assert "hot->ec" in text and "dry run" in text
        assert vs.store.find_volume(vid) is not None
        assert vs.store.find_ec_volume(vid) is None

        # -- sweep 1: hot -> ec ------------------------------------------
        text = _sh(c, f"lifecycle.apply -policy {c['policy']}")
        assert "1 done" in text, text
        assert vs.store.find_ec_volume(vid) is not None
        assert vs.store.find_volume(vid) is None
        wait_until(lambda: master.topo.lookup_ec(vid),
                   msg="ec shards registered")
        mc.refresh_lookup(vid)
        for fid, data in payloads.items():
            assert operation.read(mc, fid) == data

        # -- sweep 2: ec -> remote ---------------------------------------
        text = _sh(c, f"lifecycle.apply -policy {c['policy']}")
        assert "1 done" in text, text
        ev = vs.store.find_ec_volume(vid)
        assert ev.remote_shard_ids() == sorted(ev.shards)
        assert os.listdir(c["remote"])
        # cold GET reads through the remote backend byte-identical
        for fid, data in payloads.items():
            assert operation.read(mc, fid) == data
        assert ev.remote_reads() >= 3

        # -- sweep 3: remote -> ec (promote on heat) ---------------------
        text = _sh(c, f"lifecycle.apply -policy {c['policy']}")
        assert "1 done" in text, text
        ev = vs.store.find_ec_volume(vid)
        assert ev.remote_shard_ids() == []
        for fid, data in payloads.items():
            assert operation.read(mc, fid) == data

        # -- observability -----------------------------------------------
        from seaweedfs_tpu.ops import events
        kinds = [e["attrs"].get("kind") for e in events.JOURNAL.snapshot(
            etype="lifecycle.transition")]
        assert {"encode", "offload", "promote"} <= set(kinds)
        from seaweedfs_tpu.stats import LIFECYCLE_TRANSITIONS
        assert LIFECYCLE_TRANSITIONS.value("hot", "ec") >= 1
        assert LIFECYCLE_TRANSITIONS.value("ec", "remote") >= 1
        assert LIFECYCLE_TRANSITIONS.value("remote", "ec") >= 1
        # volume server heat report
        rep = http_util.get(
            f"http://127.0.0.1:{vs.port}/debug/lifecycle",
            timeout=5).json()
        assert str(vid) in rep["ec_volumes"]
        assert rep["ec_volumes"][str(vid)]["local_shards"]
        # master lifecycle status: policy-less master still answers
        mrep = http_util.get(
            f"http://127.0.0.1:{c['mhttp']}/debug/lifecycle",
            timeout=5).json()
        assert mrep["policy"] is None and "recent" in mrep
        # shell status walks the census
        text = _sh(c, "lifecycle.status")
        assert "tier census" in text

    def test_destroy_time_stamp_via_http(self, lifecycle_cluster):
        """POST /debug/lifecycle stamps a DestroyTime into the .vif —
        the executor's TTL verb — and the reap honors it exactly."""
        from conftest import wait_until
        from seaweedfs_tpu.client import http_util, operation
        c = lifecycle_cluster
        vs, mc = c["vs"], c["mc"]
        r = operation.submit(mc, b"ttl bound", collection="ttl")
        vid = int(r.fid.split(",")[0])
        wait_until(lambda: c["master"].topo.lookup(vid),
                   msg="ttl vol registered")
        _sh(c, "lock")
        _sh(c, f"ec.encode -volumeId {vid}")
        ev = vs.store.find_ec_volume(vid)
        assert ev is not None
        # the executor's path: the AUTHENTICATED gRPC verb (message
        # reuse: since_ns = DestroyTime in ns)
        from seaweedfs_tpu.pb import volume_server_pb2 as vpb
        from seaweedfs_tpu.utils.rpc import Stub, VOLUME_SERVICE
        grpc_at = time.time() + 7200
        Stub(f"127.0.0.1:{vs.grpc_port}", VOLUME_SERVICE).call(
            "VolumeEcShardsSetDestroyTime",
            vpb.VolumeTailReceiverRequest(volume_id=vid,
                                          since_ns=int(grpc_at * 1e9),
                                          source_volume_server="ttl"),
            vpb.VolumeTailReceiverResponse)
        assert abs(vs.store.find_ec_volume(vid).destroy_time
                   - grpc_at) < 1e-3
        # the operator's path: POST /debug/lifecycle overrides it
        at = time.time() + 3600
        resp = http_util.post(
            f"http://127.0.0.1:{vs.port}/debug/lifecycle",
            body=json.dumps({"volume": vid,
                             "destroy_time": at}).encode())
        assert resp.ok
        assert vs.store.find_ec_volume(vid).destroy_time == at
        from seaweedfs_tpu.ec import files as ec_files
        assert ec_files.read_vif(ev.base + ".vif")["destroy_time"] == at
        # boundary: reaps AT the instant, not before
        assert vs.store.delete_expired_ec_volumes(now=at - 0.001) == []
        recs = vs.store.delete_expired_ec_volumes(now=at)
        assert [x["vid"] for x in recs] == [vid]
        # restore before the grace expires; payload intact
        vs.store.restore_ec_volume_from_trash(vid, "ttl")
        mc.refresh_lookup(vid)
        assert operation.read(mc, r.fid) == b"ttl bound"
