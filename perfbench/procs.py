"""Children, ports and /metrics pages: the run's plumbing.  No jax here.

Copied in substance from ``chip_smoke.py`` (PR 21), which stays the
bring-up proof; the benchmark keeps its own so that no later PR can move it.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time


class RunFailure(Exception):
    """A phase failed: the run ends non-zero and prints no result."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Children:
    """Every process the run starts, its log, and its orderly end."""

    def __init__(self, root: str, log_dir: str, env: dict, time_limit_s: float):
        self.root = root
        self.log_dir = log_dir
        self.env = env
        self.t0 = time.monotonic()
        self.time_limit_s = time_limit_s
        self.children: list[tuple[str, subprocess.Popen]] = []
        os.makedirs(log_dir, exist_ok=True)

    def remaining(self) -> float:
        left = self.time_limit_s - (time.monotonic() - self.t0)
        if left <= 0:
            raise RunFailure(f"time limit of {self.time_limit_s:.0f}s exceeded")
        return left

    def spawn(self, name: str, argv: list[str], env: dict | None = None) -> subprocess.Popen:
        with open(os.path.join(self.log_dir, f"{name}.log"), "w") as log:
            proc = subprocess.Popen(
                [sys.executable, *argv], cwd=self.root, env=env or self.env,
                stdout=log, stderr=subprocess.STDOUT, start_new_session=True,
            )
        self.children.append((name, proc))
        return proc

    def log_tail(self, name: str, n: int = 30) -> str:
        try:
            with open(os.path.join(self.log_dir, f"{name}.log"), errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def wait_exit(self, name: str, proc: subprocess.Popen) -> None:
        try:
            rc = proc.wait(timeout=self.remaining())
        except subprocess.TimeoutExpired:
            raise RunFailure(f"{name} did not finish inside the time limit") from None
        if rc != 0:
            raise RunFailure(f"{name} exited rc={rc}:\n{self.log_tail(name)}")

    def stop(self, name: str, proc: subprocess.Popen, grace_s: float = 40.0) -> None:
        """SIGTERM (the servers drain), SIGKILL to the group if ignored; the
        chip is free for the next holder only once the process is gone."""
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait(timeout=10)
                raise RunFailure(f"{name} ignored SIGTERM for {grace_s:.0f}s") from None
        if proc.returncode != 0:
            raise RunFailure(f"{name} did not exit cleanly (rc={proc.returncode}):\n"
                             f"{self.log_tail(name)}")

    def kill_all(self) -> None:
        for _name, proc in self.children:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass


def connect(base: str, timeout: float) -> http.client.HTTPConnection:
    """A connection to ``http://host:port``."""
    host, port = base.split("//")[1].split(":")
    return http.client.HTTPConnection(host, int(port), timeout=timeout)


def http_get(base: str, path: str, timeout: float = 10.0) -> tuple[int, bytes]:
    conn = connect(base, timeout)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


def get_json(base: str, path: str, timeout: float = 10.0):
    status, body = http_get(base, path, timeout)
    if status != 200:
        raise RunFailure(f"GET {base}{path} -> {status}: {body[:300]!r}")
    return json.loads(body)


def wait_ready(children: Children, name: str, proc: subprocess.Popen, base: str,
               path: str = "/readyz") -> str:
    """Poll until 200; the child dying first is a failure."""
    while True:
        children.remaining()
        if proc.poll() is not None:
            raise RunFailure(f"{name} died before ready (rc={proc.returncode}):\n"
                             f"{children.log_tail(name)}")
        try:
            status, body = http_get(base, path, timeout=2)
            if status == 200:
                return body.decode(errors="replace")
        except (OSError, http.client.HTTPException):
            pass
        time.sleep(0.25)


def parse_metrics(text: str) -> dict[str, float]:
    """A /metrics page as {series name: sum over its label sets}."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, value = line.rpartition(" ")
        name = head.split("{", 1)[0]
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            continue
    return out


def scrape(base: str) -> str:
    status, body = http_get(base, "/metrics")
    if status != 200:
        raise RunFailure(f"GET {base}/metrics -> {status}")
    return body.decode()
