"""`shell -c` through the master's resident shell executor
(shell/executor.py), against a real master process on free ports: the
same script gives the same stdout, stderr lines and exit code on either
path; the verb's process loads no grpc; a killed client's lease goes back
at once; concurrent verbs keep their own output; and every case the path
rule leaves out runs the verb in its own process, saying so."""

import os
import re
import socket
import subprocess
import sys
import tempfile
import time
import types

import grpc
import pytest

from benchmark.layer_metrics import _process, _timing
from seaweedfs_tpu.master.master_server import MasterServer
from seaweedfs_tpu.pb import master_pb2 as mpb
from seaweedfs_tpu.security import Guard
from seaweedfs_tpu.shell import client, executor
from seaweedfs_tpu.utils.rpc import MASTER_SERVICE, Stub

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(scope="module")
def tmpdir_env(tmp_path_factory):
    """A TMPDIR of the module's own: the master's socket lies there, and
    every client is given it."""
    d = str(tmp_path_factory.mktemp("executor"))
    return {**os.environ, "TMPDIR": d}


@pytest.fixture(scope="module")
def master(tmpdir_env):
    port = free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu", "master", "-ip",
         "127.0.0.1", "-port", str(port), "-maintenanceScripts", ""],
        cwd=REPO, env=tmpdir_env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    path = os.path.join(tmpdir_env["TMPDIR"],
                        f"seaweedfs_tpu-shell-{port}.sock")
    try:
        deadline = time.monotonic() + 60
        while not os.path.exists(path):
            assert proc.poll() is None, "the master exited"
            assert time.monotonic() < deadline, "no executor socket"
            time.sleep(0.05)
        yield {"addr": f"127.0.0.1:{port}", "port": port, "path": path}
    finally:
        proc.terminate()
        proc.wait(10)


def shell(addr, script, *extra, env, cwd=REPO, timeout=120):
    r = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu", "shell", "-master", addr,
         "-c", script, *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)
    return r.returncode, r.stdout, r.stderr


def via(err: str) -> str:
    (m,) = re.findall(r"^shell via=(\w+)$", err, re.M)
    return m


def own_lines(err: str) -> "list[str]":
    """stderr without the lines that differ by nature: the `timing`
    lines' seconds, the `process` line and the `shell via=` line."""
    return [ln for ln in err.splitlines()
            if not ln.startswith(("timing ", "process ", "shell via="))]


def lease(addr):
    """Take the admin lease as another operator would, and give it back."""
    stub = Stub(addr, MASTER_SERVICE)
    resp = stub.call("LeaseAdminToken", mpb.LeaseAdminTokenRequest(
        lock_name="admin", client_name="test"), mpb.LeaseAdminTokenResponse)
    stub.call("ReleaseAdminToken", mpb.ReleaseAdminTokenRequest(
        previous_token=resp.token, previous_lock_time=resp.lock_ts_ns,
        lock_name="admin"), mpb.ReleaseAdminTokenResponse)


# script, exit code, commands that run under a span (a `timing` line)
CASES = {
    "normal": ("lock; cluster.ps; volume.list; unlock", 0, 4),
    "exit": ("lock; exit; cluster.ps", 0, 1),
    "raises": ("lock; cluster.check -url http://127.0.0.1:1", 2, 2),
    "help": ("help", 0, 0),
    # argparse writes to sys.stdout / sys.stderr and exits: the command
    # ran under its span all the same
    "argparse help": ("fs.ls -h", 0, 1),
    "bad flag": ("volume.scrub -noSuchFlag", 2, 1),
}


@pytest.fixture(scope="module")
def both(master, tmpdir_env):
    """Each case through the executor, and in its own process (a
    `-jwtSigningKey` takes that path; this master checks no token)."""
    return {name: (shell(master["addr"], script, env=tmpdir_env),
                   shell(master["addr"], script, "-jwtSigningKey", "k",
                         env=tmpdir_env))
            for name, (script, _, _) in CASES.items()}


@pytest.mark.parametrize("case", sorted(CASES))
def test_same_output_and_exit_code_on_either_path(both, case):
    _, rc, _ = CASES[case]
    (rc_x, out_x, err_x), (rc_p, out_p, err_p) = both[case]
    assert (via(err_x), via(err_p)) == ("executor", "process")
    assert rc_x == rc_p == rc, err_x[-2000:] + err_p[-2000:]
    assert out_x == out_p
    assert own_lines(err_x) == own_lines(err_p)


@pytest.mark.parametrize("case", sorted(CASES))
def test_timing_and_process_lines_parse_on_either_path(both, case):
    _, _, spanned = CASES[case]
    for _, _, err in both[case]:
        timings = _timing.lines(err)
        assert len(timings) == spanned
        got = _process.line(err)
        assert got is not None, err[-2000:]
        assert len(got["traces"]) == spanned
        assert got["commands"] == pytest.approx(
            sum(t["total"] for t in timings), abs=0.002 * max(spanned, 1))
        # the `process` line is the last, the `via` line just before it
        lines = err.rstrip("\n").splitlines()
        assert lines[-1].startswith("process ")
        assert lines[-2].startswith("shell via=")
    verbs = [[t["verb"] for t in _timing.lines(err)]
             for _, _, err in both[case]]
    assert verbs[0] == verbs[1]


def test_a_local_file_is_the_callers(master, tmpdir_env, tmp_path):
    """A command's local file resolves against the caller's working
    directory, not the master's."""
    (tmp_path / "policy.json").write_text(
        '{"rules": [{"collection": "logs", "ec_after_s": 3600}]}')
    env = {**tmpdir_env, "PYTHONPATH": REPO}
    script = "lock; lifecycle.apply -policy policy.json -dryRun; unlock"
    (rc_x, out_x, err_x), (rc_p, out_p, err_p) = (
        shell(master["addr"], script, *extra, env=env, cwd=str(tmp_path))
        for extra in ((), ("-jwtSigningKey", "k")))
    assert (via(err_x), via(err_p)) == ("executor", "process")
    assert rc_x == rc_p == 0, err_x[-2000:] + err_p[-2000:]
    assert "dry run: 0 transitions planned" in out_x
    assert out_x == out_p


def test_a_commands_configuration_is_the_callers(master, tmpdir_env,
                                                tmp_path):
    """A command's TOML configuration is looked up on the caller's search
    path (here its SWTPU_CONFIG_DIR), not the master's: the queue this
    notification.toml names cannot be opened, and both paths say so."""
    (tmp_path / "notification.toml").write_text('queue = "gcp_pub_sub"\n')
    env = {**tmpdir_env, "SWTPU_CONFIG_DIR": str(tmp_path)}
    (rc_x, out_x, err_x), (rc_p, out_p, err_p) = (
        shell(master["addr"], "fs.meta.notify -filer 127.0.0.1:1", *extra,
              env=env) for extra in ((), ("-jwtSigningKey", "k")))
    assert (via(err_x), via(err_p)) == ("executor", "process")
    assert rc_x == rc_p == 2, err_x[-2000:] + err_p[-2000:]
    assert "requires its broker SDK" in err_x
    assert out_x == out_p == ""
    assert own_lines(err_x) == own_lines(err_p)


def test_an_executor_verb_loads_no_grpc(master, tmpdir_env):
    code = f"""
import sys
sys.argv = ["seaweedfs_tpu", "shell", "-master", {master["addr"]!r},
            "-c", "lock; cluster.ps; unlock"]
from seaweedfs_tpu.__main__ import main
rc = main()
loaded = sorted(m for m in sys.modules
                if m.startswith(("grpc", "google.protobuf",
                                 "seaweedfs_tpu.pb")))
print("rc", rc, "loaded", loaded, file=sys.stderr)
"""
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env=tmpdir_env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert via(r.stderr) == "executor"
    assert r.stderr.splitlines()[-1] == "rc 0 loaded []"
    assert r.stdout.splitlines() == [
        "locked", f"master {master['addr']} (leader: {master['addr']})",
        "unlocked"]


def test_a_killed_client_leaves_the_lease_free(master, tmpdir_env):
    script = "lock; " + "; ".join(["cluster.ps"] * 5000)
    proc = subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu", "shell", "-master",
         master["addr"], "-c", script],
        cwd=REPO, env=tmpdir_env, stdout=subprocess.DEVNULL,
        stderr=subprocess.PIPE, text=True)
    try:
        for line in proc.stderr:
            if line.startswith("timing cluster.ps "):
                break
        # the script runs on under the lease it took
        with pytest.raises(grpc.RpcError, match="held by shell"):
            lease(master["addr"])
        proc.kill()
        proc.wait(10)
        killed = time.monotonic()
        while True:
            try:
                lease(master["addr"])
                break
            except grpc.RpcError:
                # the lease expires after 60 s; given back, it is free
                # within the one call in flight
                assert time.monotonic() - killed < 5.0
                time.sleep(0.02)
    finally:
        proc.kill()
        proc.stderr.close()


def test_a_killed_client_stops_the_command_in_flight(tmp_path,
                                                     monkeypatch):
    """The script's next gRPC call after its client has gone raises: a
    command that loops on calls stops there, and the lease goes back
    though the command never reached its end."""
    from seaweedfs_tpu.shell import commands
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    calls = []

    def rpc_loop(env, args):
        while len(calls) < 6000:  # a minute of calls, were it not stopped
            env.topology()
            calls.append(time.monotonic())
            time.sleep(0.01)
    monkeypatch.setitem(commands.COMMANDS, "test.rpcloop",
                        commands.Command("test.rpcloop", "", rpc_loop))
    ms = MasterServer(ip="127.0.0.1", port=free_port(),
                      volume_size_limit_mb=64)
    ms.start()
    proc = subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu", "shell", "-master",
         ms.address, "-c", "lock; test.rpcloop"],
        cwd=REPO, env={**os.environ, "TMPDIR": str(tmp_path)},
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 60
        while len(calls) < 3:
            assert time.monotonic() < deadline, "the command never ran"
            time.sleep(0.01)
        assert "admin" in ms._admin_locks
        proc.kill()
        proc.wait(10)
        killed = time.monotonic()
        while "admin" in ms._admin_locks:
            assert time.monotonic() - killed < 5.0
            time.sleep(0.02)
        stopped = len(calls)
        time.sleep(0.3)
        assert len(calls) == stopped < 6000
    finally:
        proc.kill()
        proc.wait(10)
        ms.stop()


def test_concurrent_verbs_keep_their_own_output(master, tmpdir_env):
    scripts = {"cluster.ps": "; ".join(["cluster.ps"] * 40),
               "volume.list": "; ".join(["volume.list"] * 40)}
    procs = {verb: subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu", "shell", "-master",
         master["addr"], "-c", script],
        cwd=REPO, env=tmpdir_env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True) for verb, script in
        scripts.items()}
    got = {verb: p.communicate(timeout=120) for verb, p in procs.items()}
    for verb, (out, err) in got.items():
        assert procs[verb].returncode == 0, err[-2000:]
        assert via(err) == "executor"
        assert [t["verb"] for t in _timing.lines(err)] == [verb] * 40
        assert len(_process.line(err)["traces"]) == 40
    # cluster.ps prints a line a run; volume.list of an empty cluster none
    assert got["cluster.ps"][0].splitlines() == [
        f"master {master['addr']} (leader: {master['addr']})"] * 40
    assert got["volume.list"][0] == ""


def stale_socket(d, port):
    path = os.path.join(d, f"seaweedfs_tpu-shell-{port}.sock")
    s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    s.bind(path)
    s.close()  # the file stays, no one listens
    return path


@pytest.mark.parametrize("case", [
    "stale socket file", "no socket", "remote master", "jwtSigningKey",
    "security.toml [grpc]"])
def test_the_cases_left_out_run_in_their_own_process(master, tmpdir_env,
                                                      tmp_path, case):
    env, addr, extra = dict(tmpdir_env), master["addr"], ()
    if case == "stale socket file":
        port = free_port()
        stale_socket(env["TMPDIR"], port)
        addr = f"127.0.0.1:{port}"
    elif case == "no socket":
        addr = f"127.0.0.1:{free_port()}"
    elif case == "remote master":
        addr = f"192.0.2.1:{master['port']}"
    elif case == "jwtSigningKey":
        extra = ("-jwtSigningKey", "k")
    else:
        for name in ("ca", "cert", "key"):
            (tmp_path / f"{name}.pem").write_text("not read before a dial")
        (tmp_path / "security.toml").write_text(
            "[grpc]\n" + "".join(f'{name} = "{tmp_path / name}.pem"\n'
                                 for name in ("ca", "cert", "key")))
        env["SWTPU_CONFIG_DIR"] = str(tmp_path)
    rc, out, err = shell(addr, "help", *extra, env=env)
    assert rc == 0, err[-2000:]
    assert "  lock " in out
    assert via(err) == "process"
    assert err.rstrip("\n").splitlines()[-1].startswith("process ")


@pytest.mark.parametrize("side", ["client", "executor"])
def test_a_peer_of_another_user_gets_no_script(tmp_path, monkeypatch,
                                               capsys, side):
    """A socket at the path that another user listens on (the temp
    directory is shared) gets no script: the client takes its own process.
    A client of another user that reached the master's socket gets
    nothing run. The other user is injected as the peer's uid."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    stranger = os.geteuid() + 1  # neither this user nor root
    monkeypatch.setattr(client if side == "client" else executor,
                        "peer_uid", lambda sock: stranger)
    ran = []

    def run_script(req, wire, gone):
        ran.append(req)
        return types.SimpleNamespace(ran=[], first_at=0.0, last_at=0.0), 0
    monkeypatch.setattr(executor, "_run_script", run_script)
    port = free_port()
    ex = executor.ShellExecutor(port).start()
    try:
        rc = client.client_main(
            ["-master", f"127.0.0.1:{port}", "-c", "lock; unlock"],
            time.perf_counter())
    finally:
        ex.stop()
    out, err = capsys.readouterr()
    assert out == ""
    if side == "client":
        assert rc is None  # `__main__.run_shell` runs the verb
        assert err == ""
    else:
        assert rc == 2
        assert "closed the connection" in err
    assert ran == []


def test_the_peers_user_is_read_from_the_kernel():
    a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
    with a, b:
        assert client.peer_uid(a) == client.peer_uid(b) == os.geteuid()
    assert client.trusted(os.geteuid()) and client.trusted(0)
    assert not client.trusted(os.geteuid() + 1)
    assert not client.trusted(None)


@pytest.mark.parametrize("master_arg,port", [
    ("127.0.0.1:9333", 9333), ("localhost:19333", 19333),
    ("127.0.0.2:9333", 9333), ("[::1]:9333", 9333),
    ("10.0.0.5:9333", None), ("master.example:9333", None),
    ("127.0.0.1:9333,127.0.0.1:9334", None), ("127.0.0.1", None),
    ("127.0.0.256:9333", None), ("127.example:9333", None)])
def test_only_a_single_loopback_master_is_local(master_arg, port):
    assert client._local_port(master_arg) == port


def test_the_socket_is_0600_and_goes_with_the_master(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    port = free_port()
    stale = stale_socket(str(tmp_path), port)
    ms = MasterServer(ip="127.0.0.1", port=port, volume_size_limit_mb=64)
    ms.start()
    try:
        st = os.stat(stale)
        assert st.st_mode & 0o777 == 0o600
        assert st.st_ino == ms._shell._inode  # the stale file replaced
    finally:
        ms.stop()
    assert not os.path.exists(stale)


def test_a_master_with_a_signing_key_runs_no_executor(tmp_path,
                                                      monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    ms = MasterServer(ip="127.0.0.1", port=free_port(),
                      volume_size_limit_mb=64, guard=Guard(signing_key="k"))
    ms.start()
    try:
        assert ms._shell is None
        assert os.listdir(tmp_path) == []
    finally:
        ms.stop()
        from seaweedfs_tpu.utils import rpc
        rpc._cluster_key = ""


def test_a_long_tempdir_still_reaches_the_socket(tmp_path, monkeypatch):
    """Past the 108 bytes of sun_path, bind and connect go through the
    directory's descriptor."""
    deep = tmp_path / ("d" * 60) / ("e" * 60)
    deep.mkdir(parents=True)
    monkeypatch.setattr(tempfile, "tempdir", str(deep))
    port = free_port()
    assert len(client.socket_path(port)) > 108
    ex = executor.ShellExecutor(port).start()
    try:
        rc, out, err = shell(f"127.0.0.1:{port}", "help",
                             env={**os.environ, "TMPDIR": str(deep)})
        assert rc == 0, err[-2000:]
        assert via(err) == "executor" and "  lock " in out
    finally:
        ex.stop()
    assert os.listdir(deep) == []
