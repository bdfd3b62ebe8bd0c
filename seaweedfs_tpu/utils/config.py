"""TOML configuration tiers (reference util/config.go:37-48).

`load_config("security")` searches, first hit wins:

    ./security.toml
    ~/.seaweedfs/security.toml
    /usr/local/etc/seaweedfs/security.toml
    /etc/seaweedfs/security.toml

plus an env override SWTPU_CONFIG_DIR prepended to the chain (handy for
tests and containers). Values are plain dicts; `get_dotted` resolves
"jwt.signing.key"-style paths like viper's GetString.
"""

from __future__ import annotations

import os

SEARCH_DIRS = [
    ".",
    os.path.join(os.path.expanduser("~"), ".seaweedfs"),
    "/usr/local/etc/seaweedfs",
    "/etc/seaweedfs",
]


def search_dirs() -> list[str]:
    extra = os.environ.get("SWTPU_CONFIG_DIR")
    return ([extra] if extra else []) + SEARCH_DIRS


def find_config(name: str, dirs: "list[str] | None" = None) -> str | None:
    for d in search_dirs() if dirs is None else dirs:
        path = os.path.join(d, f"{name}.toml")
        if os.path.isfile(path):
            return path
    return None


def load_config(name: str, dirs: "list[str] | None" = None) -> dict:
    """Parse the first `<name>.toml` on the tier chain, or on `dirs` in
    its place ({} if none)."""
    path = find_config(name, dirs)
    if path is None:
        return {}
    import tomllib  # only where there is a file: a `shell -c` has none
    with open(path, "rb") as f:
        return tomllib.load(f)


def get_dotted(conf: dict, key: str, default=None):
    """Resolve 'a.b.c' through nested tables; tolerate flat 'a.b.c' keys
    too (viper accepts both spellings)."""
    if key in conf:
        return conf[key]
    cur = conf
    for part in key.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return default
        cur = cur[part]
    return cur


def grpc_tls() -> "tuple[str, str, str, str]":
    """security.toml [grpc] ca, cert, key and server_name on the tier
    chain, "" where unset (server_name "swtpu"). Needs no grpc: a `shell
    -c` client reads it to choose its path before anything loads grpc."""
    sec = load_config("security")
    return (get_dotted(sec, "grpc.ca", "") or "",
            get_dotted(sec, "grpc.cert", "") or "",
            get_dotted(sec, "grpc.key", "") or "",
            get_dotted(sec, "grpc.server_name", "swtpu"))
