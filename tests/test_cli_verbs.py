"""New CLI verbs: filer.cat, filer.meta.backup, filer.replicate,
filer.remote.sync, filer.remote.gateway, fuse, autocomplete.

Reference: weed/command/filer_cat.go, filer_meta_backup.go,
filer_replicate.go, filer_remote_sync.go, filer_remote_gateway.go,
fuse.go, autocomplete.go. Long-running verbs are driven as subprocesses
with side-effect assertions (the loops have no in-process stop hook,
matching the daemons they are).
"""

import os
import socket
import subprocess
import sys
import time

import pytest

from conftest import free_port_pair


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.fixture()
def stack(tmp_path):
    import requests

    from seaweedfs_tpu.filer.filer_server import FilerServer
    from seaweedfs_tpu.master.master_server import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.storage.disk_location import DiskLocation
    from seaweedfs_tpu.storage.store import Store

    ms = MasterServer(port=free_port(), pulse_seconds=0.3,
                      maintenance_scripts=[])
    ms.start()
    vdir = tmp_path / "vol"
    vdir.mkdir()
    vport = free_port()
    store = Store("127.0.0.1", vport, "",
                  [DiskLocation(str(vdir), max_volume_count=10)],
                  coder_name="numpy")
    vs = VolumeServer(store, ms.address, port=vport, grpc_port=free_port(),
                      pulse_seconds=0.3)
    vs.start()
    from conftest import wait_cluster_up
    wait_cluster_up(ms, [vs])
    fport = free_port_pair()
    fs = FilerServer(ms.address, store_spec="memory", port=fport,
                     grpc_port=fport + 10000, chunk_size_mb=1)
    fs.start()
    yield {"ms": ms, "vs": vs, "fs": fs}
    fs.stop()
    vs.stop()
    ms.stop()


def _run_verb(args, timeout=20, **kw):
    return subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu", *args],
        capture_output=True, timeout=timeout, cwd="/root/repo", **kw)


def _spawn_verb(args, **kw):
    env = dict(os.environ, **kw.pop("env", {}))
    return subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu", *args],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        cwd="/root/repo", env=env, **kw)


def _wait_ready(proc, marker: bytes, timeout=30.0):
    """Block until the subprocess prints its ready line (the verbs
    subscribe from their own boot timestamp, so writes made before
    readiness would fall outside the subscription window)."""
    import select
    deadline = time.time() + timeout
    buf = b""
    os.set_blocking(proc.stdout.fileno(), False)
    while time.time() < deadline:
        r, _, _ = select.select([proc.stdout], [], [], 0.2)
        if r:
            chunk = proc.stdout.read() or b""
            buf += chunk
            if marker in buf:
                return buf
        if proc.poll() is not None:
            raise AssertionError(f"verb exited early: {buf.decode()}")
    raise AssertionError(f"ready marker {marker!r} not seen: {buf.decode()}")


def _wait(cond, timeout=15.0, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out: {msg}")


def test_filer_cat(stack):
    fs = stack["fs"]
    fs.write_file("/cat/hello.txt", b"cat me if you can")
    r = _run_verb(["filer.cat", "-filer", fs.url, "/cat/hello.txt"])
    assert r.returncode == 0, r.stderr
    assert r.stdout == b"cat me if you can"
    r = _run_verb(["filer.cat", "-filer", fs.url, "/cat/missing.txt"])
    assert r.returncode == 1


def test_filer_meta_backup(stack, tmp_path):
    """Full scan then tail; restart resumes from the stored offset."""
    from seaweedfs_tpu.filer.store import SqliteStore

    fs = stack["fs"]
    fs.write_file("/mb/one.txt", b"first")
    db = str(tmp_path / "meta.db")
    proc = _spawn_verb(["filer.meta.backup", "-filer", fs.url,
                        "-store", db, "-path", "/mb"])
    try:
        _wait(lambda: os.path.exists(db) and
              SqliteStore(db).find_entry("/mb", "one.txt") is not None,
              msg="scan captured one.txt")
        fs.write_file("/mb/two.txt", b"second")
        _wait(lambda: SqliteStore(db).find_entry("/mb", "two.txt")
              is not None, msg="tail captured two.txt")
    finally:
        proc.terminate()
        proc.wait(timeout=10)
    # offset was persisted: a fresh run must NOT rescan (it tails only)
    store = SqliteStore(db)
    assert store.kv_get(b"meta.backup.offset") is not None


def test_filer_replicate_logfile_queue(stack, tmp_path):
    """Events captured via fs.meta.notify into a logfile queue replay
    through the local sink (reference filer.replicate)."""
    import io

    from seaweedfs_tpu.shell import fs_commands  # noqa: F401
    from seaweedfs_tpu.shell.commands import CommandEnv, run_command

    fs = stack["fs"]
    fs.write_file("/rep/a.txt", b"alpha")
    fs.write_file("/rep/sub/b.txt", b"beta")
    qpath = tmp_path / "events.log"
    out = io.StringIO()
    env = CommandEnv(stack["ms"].address, out=out)
    env.option["filer"] = fs.url
    run_command(env, f"fs.meta.notify -dir /rep -queue logfile:{qpath}")
    env.mc.stop()
    mirror = tmp_path / "mirror"
    proc = _spawn_verb(["filer.replicate", "-filer", fs.url,
                        "-queue", f"logfile:{qpath}",
                        "-sink", f"local:{mirror}"])
    def _mirrored(path, want):
        # the sink creates the file before streaming content into it:
        # existence alone races the write — wait for the bytes
        try:
            return path.read_bytes() == want
        except OSError:
            return False

    try:
        _wait(lambda: _mirrored(mirror / "rep/a.txt", b"alpha") and
              _mirrored(mirror / "rep/sub/b.txt", b"beta"), timeout=30,
              msg="mirror populated")  # child interpreter boot can be slow
              # on this 1-core box when the full suite runs alongside
        assert (mirror / "rep/a.txt").read_bytes() == b"alpha"
        assert (mirror / "rep/sub/b.txt").read_bytes() == b"beta"
    finally:
        proc.terminate()
        proc.wait(timeout=10)
    # offset file advanced past the applied records
    assert int((tmp_path / "events.log.offset").read_text()) > 0


def test_filer_remote_sync(stack, tmp_path):
    """Local writes under a remote mount flow back to the remote store
    (reference filer.remote.sync)."""
    from seaweedfs_tpu.client.filer_client import FilerClient
    from seaweedfs_tpu.remote import mount_remote

    fs = stack["fs"]
    root = tmp_path / "cloud"
    (root / "data").mkdir(parents=True)
    (root / "data" / "seed.txt").write_text("seeded")
    fc = FilerClient(fs.url)
    mount_remote(fc, "/clouddata", f"local:{root}/data")
    proc = _spawn_verb(["filer.remote.sync", "-filer", fs.url])
    try:
        _wait_ready(proc, b"remote-sync watching")
        fs.write_file("/clouddata/new.txt", b"written locally")
        _wait(lambda: (root / "data" / "new.txt").exists(),
              msg="write-back upload")
        assert (root / "data" / "new.txt").read_bytes() == \
            b"written locally"
        fs.filer.delete_entry("/clouddata", "new.txt")
        _wait(lambda: not (root / "data" / "new.txt").exists(),
              msg="write-back delete")
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_filer_remote_gateway(stack, tmp_path):
    """Bucket creation under /buckets creates the bucket remotely and
    mounts it; deletion removes it (reference filer.remote.gateway)."""
    from seaweedfs_tpu.client.filer_client import FilerClient
    from seaweedfs_tpu.remote.remote_mount import _load_mappings

    fs = stack["fs"]
    root = tmp_path / "cloudbk"
    root.mkdir()
    proc = _spawn_verb(["filer.remote.gateway", "-filer", fs.url,
                        "-createBucketAt", f"local:{root}"])
    try:
        _wait_ready(proc, b"remote-gateway:")
        from seaweedfs_tpu.pb import filer_pb2 as fpb
        fs.filer.create_entry("/buckets", fpb.Entry(
            name="gwbkt", is_directory=True))
        _wait(lambda: (root / "gwbkt").is_dir(), msg="bucket created")
        fc = FilerClient(fs.url)
        _wait(lambda: "/buckets/gwbkt" in _load_mappings(fc),
              msg="mapping registered")
        # content under the bucket flows to the remote
        fs.write_file("/buckets/gwbkt/obj.bin", b"gw object")
        _wait(lambda: (root / "gwbkt" / "obj.bin").exists(),
              msg="object synced")
        fs.filer.delete_entry("/buckets", "gwbkt", is_recursive=True)
        _wait(lambda: not (root / "gwbkt").exists(), msg="bucket deleted")
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_autocomplete_install_remove(tmp_path, monkeypatch):
    monkeypatch.setenv("HOME", str(tmp_path))
    r = _run_verb(["autocomplete"], env={**os.environ,
                                         "HOME": str(tmp_path)})
    assert r.returncode == 0, r.stdout
    rc = (tmp_path / ".bashrc").read_text()
    assert "complete -W" in rc and "filer.replicate" in rc
    r = _run_verb(["autocomplete"], env={**os.environ,
                                         "HOME": str(tmp_path)})
    assert b"already installed" in r.stdout
    r = _run_verb(["unautocomplete"], env={**os.environ,
                                           "HOME": str(tmp_path)})
    assert b"removed" in r.stdout
    assert "complete -W" not in (tmp_path / ".bashrc").read_text()


def test_remote_sync_rename_and_meta_only(stack, tmp_path):
    """Rename of a remote-only file copies it remote-side before the
    delete (no data loss); chmod-style metadata updates don't re-upload."""
    from seaweedfs_tpu.client.filer_client import FilerClient
    from seaweedfs_tpu.pb import filer_pb2 as fpb
    from seaweedfs_tpu.remote import mount_remote
    from seaweedfs_tpu.remote.remote_mount import (_load_mappings,
                                                   apply_event_to_remote)

    fs = stack["fs"]
    root = tmp_path / "cloud2"
    (root / "d").mkdir(parents=True)
    (root / "d" / "orig.txt").write_text("remote only bytes")
    fc = FilerClient(fs.url)
    mount_remote(fc, "/rsync2", f"local:{root}/d")
    mappings = _load_mappings(fc)
    entry = fs.filer.find_entry("/rsync2", "orig.txt")
    assert entry is not None and not entry.chunks
    # simulate the rename event the filer would emit
    renamed = fpb.Entry()
    renamed.CopyFrom(entry)
    renamed.name = "renamed.txt"
    ev = fpb.EventNotification(old_entry=entry, new_entry=renamed,
                               new_parent_path="/rsync2")
    act = apply_event_to_remote(fc, mappings, "/rsync2", ev)
    assert "copy" in act and "delete" in act, act
    assert (root / "d" / "renamed.txt").read_text() == "remote only bytes"
    assert not (root / "d" / "orig.txt").exists()
    # metadata-only update (same chunk list) must not re-upload
    local = fs.write_file("/rsync2/local.bin", b"cached")
    e1 = fs.filer.find_entry("/rsync2", "local.bin")
    e2 = fpb.Entry()
    e2.CopyFrom(e1)
    e2.attributes.file_mode = 0o600
    ev2 = fpb.EventNotification(old_entry=e1, new_entry=e2)
    act2 = apply_event_to_remote(fc, mappings, "/rsync2", ev2)
    assert act2 is None, act2
