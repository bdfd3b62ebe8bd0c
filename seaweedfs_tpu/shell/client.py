"""The verb's side of `shell -c` through the master's resident shell
executor (shell/executor.py): the path rule, the wire's client end and
the `process` line. Stdlib only, and not even all of `socket`: a verb's
process pays for every module it compiles and imports.

The path (`client_main`, called by `__main__.main` before it sets up TLS,
which loads grpc): the executor only when `-c` was given, `-master` is a
single loopback `host:port`, no `-jwtSigningKey` was given, security.toml
has no [grpc] TLS, the master's socket (`socket_path`) connects and the
process listening there is this user's or root's (`trusted`): the path
lies in a shared temp directory, where another user may have bound it
first. Otherwise, and in the REPL, `__main__.run_shell` runs the verb in
its own process. Both paths end with the same stderr lines: `shell
via=executor|process`, then the `process` line.

Wire: the client sends one JSON line {master, filer, script, cwd,
config_dirs}: the commands resolve the local files they name against its
working directory (`CommandEnv.local_path`) and look up their TOML
configuration on its search path (`CommandEnv.load_config`). The rest of
the master's process is the master's: its `SWTPU_*` environment and its
retry budget and circuit breakers. The executor answers with JSON lines
["o", text] (stdout) and ["e", text] (stderr) as the script writes them,
then ["x", {...}]: the exit code and the marks of the `process` line, as
offsets from the request's arrival.
"""

from __future__ import annotations

# `socket` without its enum classes, which take ~5 ms to build
import _socket
import argparse
import contextlib
import json
import os
import sys
import tempfile
import time
import types

# sun_path holds 108 bytes, its terminating NUL among them
_SUN_PATH_MAX = 107


def shell_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="shell")
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument("-filer", default="",
                   help="default filer host:port for fs.* commands")
    p.add_argument("-jwtSigningKey", default="",
                   help="cluster signing key for gRPC auth")
    p.add_argument("-c", dest="script", default="",
                   help="run semicolon-separated commands and exit")
    return p


def socket_path(port: int) -> str:
    return os.path.join(tempfile.gettempdir(),
                        f"seaweedfs_tpu-shell-{port}.sock")


@contextlib.contextmanager
def _sun_path(path: str):
    """`path` as bind() and connect() take it: itself, or, past the
    AF_UNIX limit, the same file through an open descriptor of its
    directory (/proc/self/fd/<n>/<name>)."""
    if len(os.fsencode(path)) <= _SUN_PATH_MAX:
        yield path
        return
    fd = os.open(os.path.dirname(path), os.O_RDONLY | os.O_DIRECTORY)
    try:
        yield f"/proc/self/fd/{fd}/{os.path.basename(path)}"
    finally:
        os.close(fd)


def peer_uid(sock) -> "int | None":
    """The effective uid of the process at the other end of a connected
    UNIX socket (SO_PEERCRED: pid, uid, gid); None where the platform
    does not say."""
    try:
        cred = sock.getsockopt(_socket.SOL_SOCKET, _socket.SO_PEERCRED, 12)
    except (AttributeError, OSError):
        return None
    return int.from_bytes(cred[4:8], sys.byteorder)


def trusted(uid: "int | None") -> bool:
    """A peer this process may hand a script to, or run one for: its own
    user, or root."""
    return uid is not None and uid in (os.geteuid(), 0)


def _local_port(master: str) -> "int | None":
    """The port of a single loopback `host:port` (localhost, 127.0.0.0/8,
    ::1); None for anything else (a remote host, a list of masters, no
    port)."""
    host, _, port = master.rpartition(":")
    host = host.strip("[]")
    octets = host.split(".")
    loopback = host in ("localhost", "::1") or (
        len(octets) == 4 and octets[0] == "127"
        and all(o.isdigit() and int(o) < 256 for o in octets))
    if "," in master or not port.isdigit() or not loopback:
        return None
    return int(port)


def _tls_configured() -> bool:
    """security.toml sets [grpc] TLS (or cannot be read: the process path
    then fails closed, as it always has)."""
    from ..utils import config as cfg
    try:
        return any(cfg.grpc_tls()[:3])
    except Exception:  # noqa: BLE001 — unreadable: not the executor's to judge
        return True


def client_main(argv: "list[str]", main_at: float) -> "int | None":
    """Run `shell` argv through the master's executor and return the exit
    code; None where the verb takes its own process instead."""
    opt = shell_parser().parse_args(argv)
    if not opt.script or opt.jwtSigningKey:
        return None
    port = _local_port(opt.master)
    if port is None or _tls_configured():
        return None
    sock = _socket.socket(_socket.AF_UNIX, _socket.SOCK_STREAM)
    try:
        with _sun_path(socket_path(port)) as addr:
            sock.connect(addr)
    except OSError:
        sock.close()
        return None
    if not trusted(peer_uid(sock)):
        sock.close()  # not the master's socket: it gets no script
        return None
    from ..utils import config as cfg
    request = json.dumps({
        "master": opt.master, "filer": opt.filer, "script": opt.script,
        "cwd": os.getcwd(),
        "config_dirs": [os.path.abspath(d) for d in cfg.search_dirs()]})
    sent_at = time.perf_counter()
    try:
        sock.sendall(request.encode() + b"\n")
    except OSError:
        end = None
    else:
        end = _relay(sock)
    finally:
        sock.close()
    if end is None:
        print("error: the master's shell executor closed the connection",
              file=sys.stderr)
        end = {"rc": 2, "first": None, "last": None, "ran": []}
    marks = types.SimpleNamespace(
        first_at=0.0 if end["first"] is None else sent_at + end["first"],
        last_at=0.0 if end["last"] is None else sent_at + end["last"],
        ran=[tuple(r) for r in end["ran"]])
    print("shell via=executor", file=sys.stderr)
    print(process_line(marks, main_at), file=sys.stderr, flush=True)
    return end["rc"]


def _relay(sock) -> "dict | None":
    """Write the executor's frames to this process's stdout and stderr as
    they come; the end frame's body, or None if the connection closed
    before it."""
    buf = b""
    while True:
        try:
            chunk = sock.recv(1 << 16)
        except OSError:  # reset: as closed
            return None
        if not chunk:
            return None
        *frames, buf = (buf + chunk).split(b"\n")
        for raw in frames:
            kind, body = json.loads(raw)
            if kind == "x":
                return body
            stream = sys.stdout if kind == "o" else sys.stderr
            stream.write(body)
            stream.flush()


# -- the `process` line ------------------------------------------------------

def process_line(env, main_at: float) -> str:
    """`process start=<s> imports=<s> commands=<s> between=<s> release=<s>
    birth_ns=<wall ns> traces=<id>,...`: what one `shell -c` process did
    besides its commands, printed once after the final release_lock
    (`__main__.run_shell`, `client_main`). The five phases partition the
    process's life up to the line: `start`, birth (the kernel's start
    time of the process) to `main()` (`main_at`: interpreter, `site`, the
    package's import); `imports`, to the first command's `run_command`
    (in the process: TLS, the command modules, argparse, `CommandEnv`;
    through the executor: argparse, the connection, the request);
    `commands`, the sum of the commands' `total`s as their `timing` lines
    give them; `between`, the rest from the first command's start to the
    last one's end; `release`, from there to this line. Teardown and the
    reap come after the line: only the caller's wall sees them.
    `birth_ns` is the birth on the wall clock; `traces` are the commands'
    `shell/<name>` trace ids, in order, which their calls carry to the
    servers. `env` gives the marks: `first_at`, `last_at` (perf_counter,
    0 before any command) and `ran`, (total, trace id) a command."""
    now = time.perf_counter()
    born, birth_ns = _birth(now, main_at)
    first = env.first_at or now
    last = env.last_at or first
    commands = sum(total for total, _ in env.ran)
    phases = {"start": main_at - born, "imports": first - main_at,
              "commands": commands, "between": last - first - commands,
              "release": now - last}
    return ("process " + " ".join(f"{k}={max(0.0, v):.3f}"
                                  for k, v in phases.items())
            + f" birth_ns={birth_ns} traces="
            + ",".join(trace for _, trace in env.ran))


def _birth(now: float, main_at: float) -> "tuple[float, int]":
    """The process's birth on perf_counter's clock and in wall-clock ns:
    /proc/self/stat field 22 (clock ticks since boot) against
    CLOCK_BOOTTIME. Where that cannot be read, or reads no sane age
    (after `main_at`, or a minute before it), `main_at`."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        age = now - main_at
    if not 0.0 <= main_at - (now - age) < 60.0:
        age = now - main_at
    birth_ns = time.time_ns() - int(age * 1e9)  # swtpu-lint: disable=wallclock-duration (an instant on the wall clock, for the trace's)
    return now - age, birth_ns
