"""Config tiers, scaffold templates, profiling triggers (verdict r2 #10;
reference util/config.go:37-48, command/scaffold.go, net/http/pprof)."""

import os
import subprocess
import sys

import pytest


def test_config_tier_chain(tmp_path, monkeypatch):
    from seaweedfs_tpu.utils import config as cfg

    a = tmp_path / "a"
    b = tmp_path / "b"
    a.mkdir(), b.mkdir()
    (b / "security.toml").write_text('[jwt.signing]\nkey = "from-b"\n')
    monkeypatch.setenv("SWTPU_CONFIG_DIR", str(b))
    conf = cfg.load_config("security")
    assert cfg.get_dotted(conf, "jwt.signing.key") == "from-b"
    # first hit wins: a closer dir shadows b
    (a / "security.toml").write_text('[jwt.signing]\nkey = "from-a"\n')
    monkeypatch.setenv("SWTPU_CONFIG_DIR", str(a))
    assert cfg.get_dotted(cfg.load_config("security"),
                          "jwt.signing.key") == "from-a"
    # missing name -> {}
    assert cfg.load_config("nosuchconf") == {}
    assert cfg.get_dotted({}, "a.b.c", 42) == 42
    # flat key spelling tolerated
    assert cfg.get_dotted({"a.b": 1}, "a.b") == 1


def test_scaffold_templates_parse():
    import tomllib

    from seaweedfs_tpu.utils.scaffold import TEMPLATES

    assert set(TEMPLATES) == {"security", "master", "filer", "replication",
                              "notification", "shell"}
    for name, body in TEMPLATES.items():
        tomllib.loads(body)  # every template must be valid TOML


def test_scaffold_verb_writes_file(tmp_path):
    r = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu", "scaffold",
         "-config", "master", "-output", str(tmp_path)],
        capture_output=True, text=True, timeout=60, cwd="/root/repo")
    assert r.returncode == 0, r.stderr
    assert (tmp_path / "master.toml").exists()
    from seaweedfs_tpu.utils import config as cfg
    os.environ["SWTPU_CONFIG_DIR"] = str(tmp_path)
    try:
        conf = cfg.load_config("master")
        scripts = cfg.get_dotted(conf, "master.maintenance.scripts")
        assert "ec.rebuild" in scripts
        assert cfg.get_dotted(conf, "master.maintenance.sleep_minutes") == 17
    finally:
        del os.environ["SWTPU_CONFIG_DIR"]


def test_cpu_profile_trigger():
    from seaweedfs_tpu.utils import profiling

    import threading
    import time as _time

    stop = threading.Event()

    def busy():  # a worker thread the sampler must see
        while not stop.is_set():
            sum(i * i for i in range(1000))

    th = threading.Thread(target=busy, name="busy-worker")
    th.start()
    try:
        text = profiling.cpu_profile(seconds=0.3)
    finally:
        stop.set()
        th.join()
    assert "hottest lines" in text
    assert "busy" in text  # the OTHER thread's frames were sampled


def test_master_debug_profile_endpoint(tmp_path):
    import socket
    import time

    import requests

    from seaweedfs_tpu.master.master_server import MasterServer

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    mport, hport = free_port(), free_port()
    master = MasterServer(port=mport, http_port=hport,
                          maintenance_scripts=[])
    master.start()
    try:
        deadline = time.time() + 10
        while time.time() < deadline:
            try:
                if requests.get(f"http://127.0.0.1:{hport}/dir/status",
                                timeout=1).ok:
                    break
            except Exception:
                time.sleep(0.1)
        r = requests.get(
            f"http://127.0.0.1:{hport}/debug/profile?seconds=0.2",
            timeout=30)
        assert r.status_code == 200
        assert "hottest lines" in r.text
    finally:
        master.stop()
