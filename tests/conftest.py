"""Test harness: the suite runs on the CPU, told so through JAX_PLATFORMS
(the device gate, ops/device.py, honours exactly that), on an 8-device
virtual mesh so the multi-chip sharding (parallel/) is validated without
chips. The TPU path is exercised by chip_smoke.py on the chip.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# set before jax is imported, and inherited by every daemon a test spawns
os.environ["JAX_PLATFORMS"] = "cpu"
# Masters in fixtures run the real AdminCron; its production default now
# schedules an initial jittered sweep ~1-2 min after start, which would
# fire surprise balance/vacuum sweeps inside long-lived module fixtures.
# Pin to the legacy wait-a-full-interval behavior; tests that exercise
# the initial sweep pass initial_delay_s explicitly.
os.environ.setdefault("SWTPU_CRON_INITIAL_DELAY_S", "0")

# imported NOW, while the environment says cpu: jax reads JAX_PLATFORMS
# once, at import, and a test may monkeypatch the variable later
import jax  # noqa: E402,F401

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_fault_tolerance():
    """Circuit breakers and the retry budget are process-global (keyed by
    peer address); ports are reused across fixtures, so leaked OPEN state
    from one test must never fail-fast an unrelated test's requests."""
    from seaweedfs_tpu.utils import retry

    retry.reset_breakers()
    yield
    retry.reset_breakers()


def free_port_pair() -> int:
    """A free port whose +10000 sibling is also free and VALID (<65536) —
    the fs-command/FilerClient gRPC convention. serve() now rejects
    out-of-range ports loudly, so tests must allocate safe pairs."""
    import socket

    for _ in range(100):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        if port + 10000 >= 65536:
            continue
        try:
            probe = socket.socket()
            probe.bind(("127.0.0.1", port + 10000))
            probe.close()
            return port
        except OSError:
            continue
    raise RuntimeError("no free port pair found")


def wait_until(cond, timeout: float = 10.0, interval: float = 0.05,
               msg: str = "condition"):
    """Bounded polling instead of fixed sleeps (r2 weak #4: 68 time.sleep
    calls made the suite slow and flaky-by-design)."""
    import time as _time

    deadline = _time.time() + timeout
    while _time.time() < deadline:
        try:
            if cond():
                return
        except Exception:
            pass
        _time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


def wait_http_up(url: str, timeout: float = 10.0):
    """Block until an HTTP endpoint answers AT ALL (daemon readiness —
    a 4xx from an auth-gated root still means the server is up; any
    response proves the listener is live)."""
    import requests as _rq

    wait_until(lambda: _rq.get(url, timeout=1) is not None,
               timeout=timeout, msg=f"http up at {url}")


def wait_cluster_up(master, servers, timeout: float = 10.0):
    """Master sees every server registered AND each server answers HTTP —
    the shared fixture-readiness gate (replaces per-file poll loops)."""
    wait_until(lambda: len(master.topo.nodes) >= len(servers),
               timeout=timeout, msg=f"{len(servers)} servers registered")
    for vs in servers:
        wait_http_up(f"http://{vs.url}/status", timeout=timeout)


@pytest.hookimpl(hookwrapper=True, tryfirst=True)
def pytest_sessionfinish(session, exitstatus):
    """Leaked-server hang guard. A test that dies mid-setup (e.g. a
    server constructor raising) leaves live daemons behind, and
    concurrent.futures joins EVERY executor worker at interpreter
    shutdown — daemon flag notwithstanding (threading._register_atexit
    runs before daemon threads are abandoned). A leaked gRPC server
    always has one worker parked inside a streaming handler
    (send_heartbeat blocks on the client's next message), so shutdown
    hangs until the CI timeout kills the run. Replicate the join here
    with a bounded timeout; if workers survive it they would hang the
    real shutdown — flush and exit hard with the real status instead.
    tryfirst + hookwrapper = outermost: the post-yield below runs after
    the terminal reporter's own wrapper has printed the summary line.

    Green sessions ran every teardown and demonstrably exit clean (gRPC
    unblocks its own workers during interpreter teardown), so only a
    failing session — the one case that can leak servers — pays the
    probe."""
    yield
    if not exitstatus:
        return

    import concurrent.futures.thread as cft
    import sys
    import threading
    import time

    main = threading.main_thread()
    leaked = [t for t in threading.enumerate()
              if t is not main and t.is_alive() and not t.daemon]
    items = [(t, q) for t, q in list(cft._threads_queues.items())
             if t.is_alive()]
    for _t, q in items:
        q.put(None)  # same wake-up sentinel _python_exit would send
    deadline = time.monotonic() + 5.0
    for t, _q in items:
        t.join(max(0.0, deadline - time.monotonic()))
    hung = [t for t, _q in items if t.is_alive()]
    if leaked or hung:
        sys.stdout.write(
            f"conftest: {len(leaked)} non-daemon / {len(hung)} wedged "
            f"executor thread(s) leaked at session end — hard exit "
            f"{int(exitstatus)} to avoid the shutdown join hang\n")
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(int(exitstatus))
