"""`shell -c` against a local master: the script runs in a shell executor
resident in the master process, and the verb's own process is a thin
client of it (shell/client.py) that imports the standard library and
nothing of the package that needs grpc.

Upstream runs its maintenance scripts inside the master
(master_server.go:269 `startAdminScripts`); the executor serves the same
command machinery to the scripted verbs of operators and their crons.

Lifetime. `ShellExecutor` starts with the master's gRPC server and stops
with it (master/master_server.py), on a master that serves plaintext
gRPC without a cluster signing key: a verb's process would otherwise
dial with credentials of its own, and the master's are not lent out.
It listens on the UNIX socket `socket_path(port)`, which follows from
the master's port alone, created mode 0600: the master's user (and
root) may connect, no one else, and a connection whose peer is another
user's is closed unread (SO_PEERCRED). The client checks the listener's
user the same way before it sends a script. A stale path is unlinked at
start, and the socket removed at stop. The command modules are imported
at start, so no verb pays for them.

Which path a verb takes, and the wire: shell/client.py.

One script, one environment: each connection runs on a thread of its
own with a fresh `CommandEnv` (lock token, options, marks, output
streams, the client's working directory and configuration search path);
the channels to the master are the process's, shared, and so are its
`SWTPU_*` settings, retry budget and circuit breakers.

The lock: `lock` and `unlock` take and give back the admin lease as a
process does, and the script's end gives it back (`release_lock`, as
run_shell does). When the client's connection closes mid-script (the
client was killed), the script's next gRPC call raises
(utils/rpc.CALL_GATE), no further command starts, and the lease is given
back then: within the one call in flight, not at the lease's expiry.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import socket
import sys
import threading
import time

from .client import _sun_path, peer_uid, socket_path, trusted


def load_commands() -> None:
    """Import every command module: each registers its commands."""
    from . import (ec_commands, fs_commands,  # noqa: F401 (register)
                   lifecycle_commands, mq_commands, qos_commands,
                   remote_commands, telemetry_commands, volume_commands)


# (stdout, stderr) of the client whose script the current thread runs
_STDIO: "contextvars.ContextVar[tuple | None]" = contextvars.ContextVar(
    "swtpu_shell_stdio", default=None)


class _Routed:
    """sys.stdout or sys.stderr (and the `swtpu` log handler's stream) of
    a master with an executor: what a script's thread writes there (a
    command's argparse usage and help, a log record) goes to its client,
    as the verb's own process would have written it; any other thread's
    to the process's own stream."""

    def __init__(self, own, which: int):
        self._own = own
        self._which = which

    def _target(self):
        stdio = _STDIO.get()
        return self._own if stdio is None else stdio[self._which]

    def write(self, text: str) -> int:
        return self._target().write(text)

    def flush(self) -> None:
        self._target().flush()

    def __getattr__(self, name: str):
        return getattr(self._own, name)


def _route_stdio() -> None:
    """Install `_Routed` over this process's stdout, stderr and the
    `swtpu` log handler that writes to stderr; once a process."""
    import logging
    for name, which in (("stdout", 0), ("stderr", 1)):
        own = getattr(sys, name)
        if isinstance(own, _Routed):
            continue
        setattr(sys, name, _Routed(own, which))
        for h in logging.getLogger("swtpu").handlers:
            if isinstance(h, logging.StreamHandler) and h.stream is own:
                h.setStream(getattr(sys, name))


class _Wire:
    """One connection's frames, sent whole under a lock. A send that
    fails marks the client gone and drops the rest."""

    def __init__(self, conn: socket.socket, gone: threading.Event):
        self._conn = conn
        self._gone = gone
        self._lock = threading.Lock()

    def send(self, kind: str, body) -> None:
        frame = json.dumps([kind, body]).encode() + b"\n"
        with self._lock:
            if self._gone.is_set():
                return
            try:
                self._conn.sendall(frame)
            except OSError:
                self._gone.set()


class _Stream:
    """A text stream of the connection: whole lines go out as they are
    written, the rest on flush()."""

    def __init__(self, wire: _Wire, kind: str):
        self._wire = wire
        self._kind = kind
        self._buf = ""
        self._lock = threading.Lock()

    def write(self, text: str) -> int:
        with self._lock:
            self._buf += text
            cut = self._buf.rfind("\n") + 1
            if cut:
                self._wire.send(self._kind, self._buf[:cut])
                self._buf = self._buf[cut:]
        return len(text)

    def flush(self) -> None:
        with self._lock:
            if self._buf:
                self._wire.send(self._kind, self._buf)
                self._buf = ""


class ShellExecutor:
    """The master's resident shell executor (see the module's doc)."""

    def __init__(self, port: int):
        self.path = socket_path(port)
        self._sock: "socket.socket | None" = None
        self._inode = 0

    def start(self) -> "ShellExecutor":
        load_commands()
        _route_stdio()
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        # the file bind() makes takes the socket's mode: 0600 from birth
        os.fchmod(sock.fileno(), 0o600)
        try:
            with _sun_path(self.path) as addr:
                with contextlib.suppress(FileNotFoundError):
                    os.unlink(self.path)  # a stale socket
                sock.bind(addr)
            self._inode = os.stat(self.path).st_ino
            sock.listen(64)
        except OSError:
            sock.close()
            raise
        self._sock = sock
        threading.Thread(target=self._accept, daemon=True,
                         name="shell-executor").start()
        return self

    def stop(self) -> None:
        sock, self._sock = self._sock, None
        if sock is None:
            return
        with contextlib.suppress(OSError):
            sock.shutdown(socket.SHUT_RDWR)  # wakes accept()
        sock.close()
        with contextlib.suppress(OSError):
            if os.stat(self.path).st_ino == self._inode:  # still ours
                os.unlink(self.path)

    def _accept(self) -> None:
        sock = self._sock
        while True:
            try:
                conn, _ = sock.accept()
            except OSError:
                return  # stopped
            threading.Thread(target=self._serve, args=(conn,), daemon=True,
                             name="shell-verb").start()

    def _serve(self, conn: socket.socket) -> None:
        with conn:
            if not trusted(peer_uid(conn)):
                return
            req = _read_request(conn)
            if req is None:
                return
            recv_at = time.perf_counter()
            gone, done = threading.Event(), threading.Event()
            watcher = threading.Thread(target=_watch,
                                       args=(conn, gone, done), daemon=True,
                                       name="shell-verb-watch")
            watcher.start()
            wire = _Wire(conn, gone)
            env, rc = _run_script(req, wire, gone)
            done.set()
            wire.send("x", {
                "rc": rc, "ran": env.ran,
                "first": env.first_at - recv_at if env.first_at else None,
                "last": env.last_at - recv_at if env.last_at else None})
            with contextlib.suppress(OSError):
                conn.shutdown(socket.SHUT_RDWR)  # wakes the watcher
            watcher.join(5.0)


def _read_request(conn: socket.socket) -> "dict | None":
    buf = b""
    while not buf.endswith(b"\n"):
        try:
            got = conn.recv(1 << 16)
        except OSError:
            return None
        if not got or len(buf) > 1 << 20:
            return None
        buf += got
    try:
        req = json.loads(buf)
        return req if isinstance(req.get("script"), str) else None
    except (ValueError, AttributeError):
        return None


def _watch(conn: socket.socket, gone: threading.Event,
           done: threading.Event) -> None:
    """The client sends nothing after its request: the connection reads
    its end when the client closes it, or when the script's end shuts it."""
    try:
        while conn.recv(4096):
            pass
    except OSError:
        pass
    if not done.is_set():
        gone.set()


def _run_script(req: dict, wire: _Wire, gone: threading.Event):
    """The script as `__main__.run_shell` runs it, on a fresh CommandEnv
    writing to the connection: (env, exit code)."""
    from ..utils import rpc
    from .commands import CommandEnv, run_command
    out, err = _Stream(wire, "o"), _Stream(wire, "e")
    env = CommandEnv(req["master"], out=out, err=err, cwd=req.get("cwd", ""),
                     config_dirs=req.get("config_dirs"))
    if req.get("filer"):
        env.option["filer"] = req["filer"]
    gate = rpc.CALL_GATE.set(gone)
    stdio = _STDIO.set((out, err))
    rc = 0
    try:
        try:
            for line in req["script"].split(";"):
                if gone.is_set() or not run_command(env, line):
                    break
        except SystemExit as e:  # a command's argparse: -h, a bad flag
            rc = _exit_code(e, err)
        except Exception as e:  # noqa: BLE001 — a failed command: exit 2
            print(f"error: {e}", file=err)
            rc = 2
        rpc.CALL_GATE.reset(gate)  # the lease goes back, client or not
        try:
            env.release_lock()
        except Exception as e:  # noqa: BLE001 — as an uncaught raise would
            print(f"error: releasing the admin lock: {e}", file=err)
            rc = 1
    finally:
        _STDIO.reset(stdio)
        out.flush()
        err.flush()
    return env, rc


def _exit_code(e: SystemExit, err) -> int:
    """The exit code SystemExit(e.code) gives a process."""
    if e.code is None or isinstance(e.code, int):
        return e.code or 0
    print(e.code, file=err)
    return 1
