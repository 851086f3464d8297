"""The gateway server as a child process, spoken to in NDJSON.

Copied from the program's ``chip_smoke.py`` (``Server`` and its stage
parser), so that the yardstick stays put when the program changes.  The
parent never imports jax: the child is the one process on the chip.
Replies are routed to their callers by ``id`` (requests) or ``cmd``
(control verbs), so several client threads can wait at once.
"""
from __future__ import annotations

import ast
import collections
import json
import os
import re
import subprocess
import sys
import threading
import time


class ServerError(RuntimeError):
    """The child failed, exited or missed a deadline."""


class Server:
    """One ``python -m repro.launch.estimate --serve --gateway`` child."""

    def __init__(self, root: str, flags: list, env: dict, deadline: float,
                 log: str | None = None):
        self.deadline = deadline          # time.monotonic() value
        self._log = open(log, "w") if log else None
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "repro.launch.estimate", "--serve",
             "--gateway", *flags],
            cwd=root, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, bufsize=1)
        self.stderr: collections.deque = collections.deque(maxlen=200)
        self.banner: str | None = None    # the child's "serving ..." line
        self._cv = threading.Condition()
        self._replies: dict = {}          # routing key -> list of replies
        self._closed = False
        self._write = threading.Lock()
        self._readers = [threading.Thread(target=self._pump_out, daemon=True),
                         threading.Thread(target=self._pump_err, daemon=True)]
        for t in self._readers:
            t.start()

    def _pump_out(self) -> None:
        for line in self.proc.stdout:
            try:
                r = json.loads(line)
            except ValueError:
                continue
            if r.get("progress"):
                continue
            key = ("id", r["id"]) if "id" in r else ("cmd", r.get("cmd"))
            with self._cv:
                self._replies.setdefault(key, []).append(
                    (time.monotonic(), r))
                self._cv.notify_all()
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def _pump_err(self) -> None:
        for line in self.proc.stderr:
            self.stderr.append(line.rstrip("\n"))
            if self.banner is None and line.startswith("serving "):
                with self._cv:
                    self.banner = line.rstrip("\n")
                    self._cv.notify_all()
            if self._log is not None:
                self._log.write(line)

    def fail(self, why: str):
        tail = "\n".join(ln for ln in list(self.stderr)[-30:] if ln)
        raise ServerError(f"{why}\n--- server stderr (tail) ---\n{tail}")

    def send(self, *objs: dict) -> float:
        """Write the lines in one write; return the monotonic send time."""
        with self._write:
            t = time.monotonic()
            try:
                self.proc.stdin.write("".join(json.dumps(o) + "\n"
                                              for o in objs))
                self.proc.stdin.flush()
            except OSError as e:
                self.fail(f"server gone before {objs[0]}: {e}")
        return t

    def wait(self, key: tuple, until: float | None = None) -> tuple:
        """``(arrival time, reply)`` for routing key ``key``, oldest first;
        raises past ``until`` (default: the server's deadline)."""
        until = self.deadline if until is None else min(until, self.deadline)
        with self._cv:
            while True:
                got = self._replies.get(key)
                if got:
                    out = got.pop(0)
                    if not got:
                        del self._replies[key]
                    return out
                if self._closed:
                    self.proc.wait(timeout=30)
                    self.fail(f"server exited (rc={self.proc.returncode}) "
                              f"before answering {key}")
                left = until - time.monotonic()
                if left <= 0:
                    raise TimeoutError(f"no reply for {key} in time")
                self._cv.wait(timeout=left)

    def wait_banner(self, timeout: float = 30.0) -> str:
        """The line the child prints when it starts serving (it names
        the mesh it serves on); call once it has answered a verb."""
        until = min(time.monotonic() + timeout, self.deadline)
        with self._cv:
            while self.banner is None:
                left = until - time.monotonic()
                if left <= 0:
                    self.fail("server printed no 'serving' line")
                self._cv.wait(timeout=left)
            return self.banner

    def call(self, obj: dict) -> dict:
        """A control verb: send it and return its ``ok`` reply."""
        self.send(obj)
        _, r = self.wait(("cmd", obj["cmd"]))
        if r.get("ok") is not True:
            self.fail(f"{obj} answered {r}")
        return r

    def stages(self) -> dict:
        """``{stage: (seconds, count)}`` from the ``repro_stage_seconds``
        histograms, and the other counters the readers use, scraped with
        the ``metrics`` verb."""
        return parse_metrics(self.call({"cmd": "metrics"})["text"])

    def close(self) -> None:
        """``quit``; the child must then exit 0 before the deadline."""
        self.call({"cmd": "quit"})
        self.proc.stdin.close()
        try:
            rc = self.proc.wait(timeout=max(1.0, self.deadline
                                            - time.monotonic()))
        except subprocess.TimeoutExpired:
            self.fail("server did not exit after quit")
        if rc != 0:
            self.fail(f"server exited with code {rc}")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        for t in self._readers:
            t.join(timeout=10)
        if self._log is not None:
            self._log.close()


def served_mesh(banner: str) -> dict | None:
    """The mesh a ``serving GATEWAY ... mesh={'data': 4} ...`` line
    names, as ``{axis: size}``; None where it serves on no mesh."""
    m = re.search(r"\bmesh=(None|\{[^}]*\})", banner)
    if m is None:
        raise ServerError(f"no mesh in the server's line {banner!r}")
    return ast.literal_eval(m.group(1))


def parse_metrics(text: str) -> dict:
    """Prometheus text -> ``{"stage": {name: [sum_s, count]},
    "lru": {event: count}, "compiles": backend compiles}`` for the
    series the benchmark reads."""
    stage: dict = {}
    lru: dict = {}
    compiles = 0.0
    for line in text.splitlines():
        for part, slot in (("_sum", 0), ("_count", 1)):
            head = f'repro_stage_seconds{part}{{stage="'
            if line.startswith(head):
                name = line[len(head):].split('"', 1)[0]
                stage.setdefault(name, [0.0, 0.0])[slot] = float(
                    line.split()[-1])
        head = 'repro_engine_window_lru_total{cache="window",event="'
        if line.startswith(head):
            lru[line[len(head):].split('"', 1)[0]] = float(line.split()[-1])
        if line.startswith("repro_engine_compiles_total{"):
            compiles += float(line.split()[-1])
    return {"stage": stage, "lru": lru, "compiles": compiles}


def child_env(root: str, platform: str, obs_level: str,
              cache_dir: str) -> dict:
    """The child's environment: the chip (no CPU fallback), telemetry
    level, the program on the path, and the compile cache ``cache_dir``
    (the benchmark's fixed one inside the checkout, or a CPU test's
    own).  ``LIBTPU_INIT_ARGS`` passes untouched."""
    env = dict(os.environ, JAX_PLATFORMS=platform, REPRO_OBS=obs_level,
               JAX_COMPILATION_CACHE_DIR=cache_dir)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(root, "src"), os.environ.get("PYTHONPATH"))
        if p)
    return env
