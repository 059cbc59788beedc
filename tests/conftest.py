"""Test env: force JAX onto a virtual 8-device CPU mesh.

Must run before jax is first imported anywhere, which pytest guarantees by
importing conftest first.  All multi-chip sharding tests run against these
virtual devices; real-TPU behavior is exercised by chip_smoke.py on the
chip, not by tests (SURVEY.md section 4: fake/CPU backend so the serving
path is testable without TPUs).

The suite does not turn the persistent compile cache on: in-process servers
(ModelServer(...)) leave it alone, only the entry points' main() functions
enable it, and the tests that drive those pass their own directory.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import ipaddress  # noqa: E402
import socket  # noqa: E402

import pytest  # noqa: E402


# --- the suite reaches nothing but this machine -----------------------------
#
# Installed while conftest is imported, not as a fixture: it has to hold for
# module-scoped servers and for every thread they start.  A test that lets a
# URL like http://img/x.png through a stubbed seam then fails at once with an
# error that names the host, not after a resolver's time-out with the
# resolver's message.  (Child processes a test starts are not covered; gRPC's
# C core does not go through Python's sockets.)


class OutsideConnectionRefused(OSError):
    """A test tried to resolve or connect to a host that is not loopback."""


_LOOPBACK_NAMES = {"", "localhost", "localhost.localdomain", "ip6-localhost"}


def _require_loopback(host, what: str) -> None:
    if isinstance(host, bytes):
        host = host.decode("ascii", "replace")
    name = (host or "").strip("[]").split("%")[0].lower()
    if name in _LOOPBACK_NAMES:
        return
    try:
        ip = ipaddress.ip_address(name)
    except ValueError:
        ip = None
    if ip is not None and (ip.is_loopback or ip.is_unspecified):
        return
    raise OutsideConnectionRefused(
        f"{what} {host!r} refused: tests reach only loopback "
        "(tests/conftest.py); stub the seam that fetches, or serve it from "
        "127.0.0.1"
    )


def _loopback_only(real, what: str, host_of):
    def guarded(*args, **kwargs):
        _require_loopback(host_of(*args, **kwargs), what)
        return real(*args, **kwargs)

    guarded.__name__ = getattr(real, "__name__", what)
    guarded.loopback_only = True
    return guarded


def _connect_host(sock, address, *_):
    inet = sock.family in (socket.AF_INET, socket.AF_INET6)
    return address[0] if inet and isinstance(address, tuple) else ""


socket.getaddrinfo = _loopback_only(
    socket.getaddrinfo, "name lookup of", lambda host, *a, **k: host
)
socket.gethostbyname = _loopback_only(
    socket.gethostbyname, "name lookup of", lambda host: host
)
socket.socket.connect = _loopback_only(
    socket.socket.connect, "connection to", _connect_host
)
socket.socket.connect_ex = _loopback_only(
    socket.socket.connect_ex, "connection to", _connect_host
)

from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec  # noqa: E402


@pytest.fixture(scope="session")
def tiny_spec() -> ModelSpec:
    """A small Xception spec so CPU tests stay fast."""
    return register_spec(
        ModelSpec(
            name="tiny-xception",
            family="xception",
            input_shape=(96, 96, 3),
            labels=("a", "b", "c", "d"),
            preprocessing="tf",
            head_hidden=(16,),
            description="test-only small-input xception",
        )
    )
