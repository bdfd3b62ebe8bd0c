"""The device gate (ops/device.py): one answer per process to "what do I
compute on", no silent host fallback anywhere on the device path, and
chip_smoke.py end to end on the CPU backend."""

import json
import os
import resource
import subprocess
import sys

import numpy as np
import pytest

from seaweedfs_tpu.ops import crc32c, device
from seaweedfs_tpu.storage.disk_location import DiskLocation
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.storage.volume import Volume

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fake_backend(monkeypatch):
    """Swap the gate's resolver: the backend 'comes up' as whatever the
    test says, without touching the real (CPU) one."""
    def install(platform, kind="fake", count=1, told=""):
        monkeypatch.setattr(device, "_info", None)
        monkeypatch.setattr(
            device, "_probe",
            lambda: device.DeviceInfo(platform, kind, count))
        monkeypatch.setenv("JAX_PLATFORMS", told)
    return install


def test_device_coder_raises_when_backend_is_not_tpu(fake_backend):
    """JAX fell back to the CPU on its own (JAX_PLATFORMS unset): a
    device coder is an error, by any of its names."""
    from seaweedfs_tpu.ops.coder import get_coder
    fake_backend("cpu")
    for name in ("jax", "pallas"):
        with pytest.raises(device.DeviceError, match="platform='cpu'"):
            get_coder(name, 4, 2)
    with pytest.raises(device.DeviceError, match="needs a TPU"):
        device.resolve_coder("mesh")
    with pytest.raises(device.DeviceError):
        Store("127.0.0.1", 1, "", [], coder_name="jax")


def test_told_cpu_is_honoured_and_tpu_never_interprets(fake_backend):
    from seaweedfs_tpu.ops.coder import JaxCoder, PallasCoder
    fake_backend("cpu", told="cpu")
    assert not JaxCoder(4, 2).use_pallas          # einsum path
    assert PallasCoder(4, 2)._interpret           # kernel, interpreted
    fake_backend("tpu", kind="TPU v5 lite", told="tpu")
    assert JaxCoder(4, 2).use_pallas
    assert not PallasCoder(4, 2)._interpret


def test_auto_resolves_once_at_store_start(fake_backend, tmp_path):
    fake_backend("tpu", kind="TPU v5 lite", told="")
    s = Store("127.0.0.1", 1, "", [DiskLocation(str(tmp_path))])
    assert s.backend == "jax"
    assert s.status()["coder"] == "jax"
    assert s.status()["platform"] == "tpu"
    assert s.status()["device_kind"] == "TPU v5 lite"
    assert s.status()["devices"] == 1
    # no TPU: a host coder, and told-CPU does not even ask jax
    fake_backend("cpu", told="")
    assert Store("127.0.0.1", 1, "", []).backend in ("native", "numpy")
    fake_backend("tpu", told="cpu")
    assert Store("127.0.0.1", 1, "", []).backend in ("native", "numpy")
    assert device.current() is None


def test_backend_that_cannot_come_up_raises(monkeypatch):
    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(device, "_info", None)
    monkeypatch.setattr(device, "_probe", boom)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(RuntimeError, match="backend 'tpu'"):
        Store("127.0.0.1", 1, "", [], coder_name="auto")
    from seaweedfs_tpu.ops import rs_pallas
    with pytest.raises(RuntimeError, match="backend 'tpu'"):
        rs_pallas.available()


def test_store_coder_propagates_coder_exception(monkeypatch):
    """No numpy retry: what the resolved coder raises reaches the RPC."""
    from seaweedfs_tpu.ops import coder as coder_mod

    class Broken(coder_mod.NumpyCoder):
        def __init__(self, d, p):
            raise RuntimeError("device coder broke")
    monkeypatch.setitem(coder_mod._REGISTRY, "numpy", Broken)
    s = Store("127.0.0.1", 1, "", [], coder_name="numpy")
    with pytest.raises(RuntimeError, match="device coder broke"):
        s.coder()
    with pytest.raises(RuntimeError, match="device coder broke"):
        s.coder(codec="piggyback")


def test_compile_cache_path_is_env_first(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert device.compile_cache_dir() == os.path.join(REPO, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert device.compile_cache_dir() == "/somewhere/else"


def test_host_coder_scrub_never_imports_jax(tmp_path):
    """A -coder numpy store scrubs on the host loop; device='on' is an
    error there, and neither loads jax into the process."""
    code = f"""
import sys
from seaweedfs_tpu.ops import device
from seaweedfs_tpu.storage.disk_location import DiskLocation
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.scrub import scrub_volume
from seaweedfs_tpu.storage.store import Store
s = Store("127.0.0.1", 1, "", [DiskLocation({str(tmp_path)!r})],
          coder_name="numpy")
v = s.add_volume(1)
for i in range(1, 30):
    v.write_needle(Needle(id=i, cookie=1, data=bytes([i]) * (i * 37)))
res = scrub_volume(v, device="auto")
assert (res.mode, res.scanned, res.corrupt) == ("cpu", 29, []), res
try:
    scrub_volume(v, device="on")
except device.DeviceError as e:
    assert "needs a TPU" in str(e)
else:
    raise AssertionError("device=on passed without a TPU")
assert s.status()["platform"] is None and not s.status()["jax_loaded"]
assert "jax" not in sys.modules, "host-coder scrub imported jax"
print("ok")
"""
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, text=True,
                       capture_output=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": ""})
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr[-2000:]


def test_host_coder_rebuild_never_imports_jax(tmp_path):
    """A host-coder seal and rebuild run their stages (tracing/stages.py)
    as plain sums: no profiler annotation, and jax is not loaded for
    one."""
    code = f"""
import os, sys
import numpy as np
from seaweedfs_tpu.ec import encoder, files, stream
from seaweedfs_tpu.ec.locate import EcGeometry
from seaweedfs_tpu.ops.coder import get_coder
from seaweedfs_tpu.tracing import stages
geo = EcGeometry(d=4, p=2, large_block=1 << 16, small_block=1 << 12)
base = os.path.join({str(tmp_path)!r}, "v")
with open(base + ".dat", "wb") as f:
    f.write(np.random.default_rng(1).integers(
        0, 256, 300000, dtype=np.uint8).tobytes())
coder = get_coder("numpy", 4, 2)
sealed = {{}}
stream.encode_volumes([(base + ".dat", base, None)], geo, coder,
                      stats=sealed)
assert sealed["mode"] == "sync" and sealed["finish_s"] > 0, sealed
os.unlink(base + files.shard_ext(0))
stats = {{}}
assert encoder.rebuild_shards(base, geo, coder, stats=stats) == [0]
assert stats["read_s"] > 0 and stats["batches"] == 1, stats
assert stages._annotation("swtpu/rebuild.read", {{}}) is None
assert "jax" not in sys.modules, "a host-coder rebuild imported jax"
print("ok")
"""
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, text=True,
                       capture_output=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": ""})
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr[-2000:]


def test_shell_verb_never_imports_jax():
    """The shell's root span, client spans and `timing` line: a verb
    (here against a master that is not there) ends without jax."""
    code = """
import contextlib, io, sys
from seaweedfs_tpu.shell import ec_commands, volume_commands
from seaweedfs_tpu.shell.commands import CommandEnv, run_command
env = CommandEnv("127.0.0.1:1", out=io.StringIO())
err = io.StringIO()
with contextlib.redirect_stderr(err):
    try:
        run_command(env, "volume.list")
    except Exception:
        pass  # nobody answers; the verb still ends with its line
assert err.getvalue().startswith("timing volume.list total="), err.getvalue()
assert " VolumeList=" in err.getvalue(), err.getvalue()
assert "jax" not in sys.modules, "a shell verb imported jax"
print("ok")
"""
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, text=True,
                       capture_output=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": ""})
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr[-2000:]


def test_scrub_dispatch_stays_under_byte_bound(tmp_path, monkeypatch):
    """One 4 MiB needle among thousands of 1 KiB ones used to pad the
    whole 4096-needle batch to 4 MiB rows (a 16 GiB allocation). Every
    dispatch is now one of a fixed set of shapes holding at most
    _DISPATCH_BYTES, and each CRC equals the host's."""
    from seaweedfs_tpu.storage import scrub

    device.info()  # this process resolves its (CPU) backend: auto = JAX
    rng = np.random.default_rng(3)
    v = Volume(str(tmp_path), "", 1)
    sizes = [1024] * 2500 + [4 << 20] + [1024] * 500 + [700, 5000, 0]
    datas = {}
    for i, size in enumerate(sizes, start=1):
        datas[i] = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        v.write_needle(Needle(id=i, cookie=1, data=datas[i]))
    shapes, received, crcs = [], [], []
    real_jit, real_block = scrub._crc_jit(), scrub._Walker.block
    real_finalize = scrub.crcmod.finalize

    def spy_jit(blocks):
        shapes.append(blocks.shape)
        return real_jit(blocks)

    def spy_block(self, msg):
        out = real_block(self, msg)
        received.append(out[1])  # the block's needle ids
        return out

    def spy_finalize(raw, lengths):
        crcs.append(real_finalize(raw, lengths))
        return crcs[-1]
    monkeypatch.setattr(scrub, "_crc_jit", lambda: spy_jit)
    monkeypatch.setattr(scrub._Walker, "block", spy_block)
    monkeypatch.setattr(scrub.crcmod, "finalize", spy_finalize)
    res = scrub.scrub_volume(v, device="auto")
    v.close()
    assert res.scanned == len(sizes)
    assert res.corrupt == [] and res.mode == "xla-cpu"
    # blocks are compared in the order the walker packed them
    assert len(received) == len(crcs) == len(shapes) == res.blocks
    for ids, out in zip(received, crcs):
        assert [int(c) for c in out[:len(ids)]] == [
            crc32c.crc32c(datas[i]) for i in ids]
    assert max(b * l for b, l in shapes) <= scrub._DISPATCH_BYTES
    assert set(shapes) == {scrub._block_shape(n) for n in set(sizes)}


def test_ec_encode_fails_loudly_when_generate_fails(tmp_path):
    """A generate RPC that raises used to print 'batch generate failed'
    and end 'ec encoded 0 volumes' with rc 0."""
    import io

    from conftest import wait_cluster_up

    from seaweedfs_tpu.client.master_client import MasterClient
    from seaweedfs_tpu.client.operation import submit
    from seaweedfs_tpu.master.master_server import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.commands import CommandEnv, run_command
    from seaweedfs_tpu.shell import ec_commands  # noqa: F401 (registers)
    from seaweedfs_tpu.storage.types import parse_file_id

    def _fp():
        import socket
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    ms = MasterServer(port=_fp(), volume_size_limit_mb=64,
                      pulse_seconds=0.5)
    ms.start()
    vp = _fp()
    store = Store("127.0.0.1", vp, "",
                  [DiskLocation(str(tmp_path / "v"), max_volume_count=8)],
                  coder_name="numpy")
    vs = VolumeServer(store, ms.address, port=vp, grpc_port=_fp(),
                      pulse_seconds=0.5)
    vs.start()
    wait_cluster_up(ms, [vs])
    mc = MasterClient(ms.address).start()
    try:
        vid, _, _ = parse_file_id(submit(mc, os.urandom(3000)).fid)

        def broken(*a, **kw):
            raise RuntimeError("device coder broke")
        store.generate_ec_shards_batch = broken
        out = io.StringIO()
        env = CommandEnv(ms.address, mc=mc, out=out)
        env.acquire_lock()
        with pytest.raises(Exception, match="device coder broke"):
            run_command(env, f"ec.encode -volumeId {vid} -ecShards 4,2")
        assert "ec encoded" not in out.getvalue()
        assert not store.find_volume(vid).read_only  # rolled back
        env.release_lock()
        # the operator-facing form: `shell -c` exits non-zero
        r = subprocess.run(
            [sys.executable, "-m", "seaweedfs_tpu", "shell", "-master",
             ms.address, "-c",
             f"lock; ec.encode -volumeId {vid} -ecShards 4,2; unlock"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert r.returncode != 0, r.stdout
        assert "device coder broke" in r.stderr
    finally:
        mc.stop()
        vs.stop()
        ms.stop()


def test_mesh_coder_runs_the_daemon_paths():
    """`-coder mesh` was broken through ec/stream (no .codec) and for a
    degraded read's single [d, L] stripe; the virtual 8-device dry run
    drives both."""
    from seaweedfs_tpu.ops.coder import NumpyCoder, get_coder

    sys.path.insert(0, REPO)
    import __graft_entry__
    __graft_entry__.dryrun_multichip(8)
    mesh = get_coder("mesh", 10, 4)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (10, 3000), dtype=np.uint8)
    full = np.concatenate([data, NumpyCoder(10, 4).encode(data)])
    present = tuple(i for i in range(14) if i != 3)[:10]
    got = np.asarray(mesh.reconstruct(full[list(present)], present, (3,)))
    assert np.array_equal(got[0], data[3])


def test_chip_smoke_sizes_itself_to_the_file_size_limit(tmp_path):
    """No limit: the size asked for. Under RLIMIT_FSIZE (the driver's chip
    machine caps files at 1 GiB, which a volume filled to a 1 GiB limit
    overruns by its last needle): a soft limit is raised to the hard one,
    a hard one brings the volume limit under it, with room for the
    overrun, and more volumes make up the data."""
    code = ("import resource, sys, json; sys.path.insert(0, {repo!r}); "
            "resource.setrlimit(resource.RLIMIT_FSIZE, {lim}); "
            "import chip_smoke; "
            "print(json.dumps(chip_smoke.plan_size('tiny', {d!r})))")

    def plan(lim):
        r = subprocess.run(
            [sys.executable, "-c",
             code.format(repo=REPO, lim=lim, d=str(tmp_path))],
            capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    inf = resource.RLIM_INFINITY
    asked = {"limit_mb": 8, "volumes": 2, "max_needle": 256 << 10}
    assert plan((inf, inf)) == asked
    assert plan((1 << 20, inf)) == asked
    cut = plan((6 << 20, 6 << 20))
    assert cut["limit_mb"] == 5 and cut["volumes"] == 4 and "cut" in cut
    assert (cut["limit_mb"] << 20) * 65 // 64 + cut["max_needle"] <= 6 << 20
    assert not list(tmp_path.iterdir())  # the probe file is gone


def test_chip_smoke_on_the_cpu_backend():
    """The whole main path through separate daemons, tiny, on the CPU
    backend, under a file size limit smaller than a full volume (as on
    the driver's chip machine); and without --allow-cpu the same command
    refuses to run where there is no chip, naming the platform and
    printing no result."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        ["bash", "-c", 'ulimit -f 6144; exec "$0" "$@"', sys.executable,
         "chip_smoke.py", "--allow-cpu", "--size", "tiny"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    last = r.stdout.strip().splitlines()[-1]
    assert '"ok": true' in last and '"platform": "cpu"' in last
    assert "CUT: files here may hold" in r.stdout
    assert "in 8 volumes of 5 MiB" in r.stdout
    assert "byte-identical to the host encode" in r.stdout
    assert "rebuilt on A" in r.stdout and "mode=async" in r.stdout

    r = subprocess.run(
        [sys.executable, "chip_smoke.py", "--size", "tiny"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "tpu" in r.stderr.lower()
