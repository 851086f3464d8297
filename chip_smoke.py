#!/usr/bin/env python3
"""Smoke run of the served estimator on a TPU: gateway -> Session -> engine.

    python chip_smoke.py               # one chip: small and real-size phases
    python chip_smoke.py --four-chips  # only the engine's 4-way data mesh

The script never imports jax.  It starts the gateway server through its
normal entry point (``python -m repro.launch.estimate --serve --gateway``)
as one child process at a time, with ``JAX_PLATFORMS=tpu`` so that a
missing chip fails the child at start-up instead of running on the CPU,
and talks NDJSON to it under a wall-clock deadline.  Each child exits
before the next one starts: a process that holds the chip keeps it.

One chip:

* ``small`` — a tenant on the graph of ``tests/golden_estimates.json``,
  served with its chunk and checkpoint settings; ``estimate``, ``valid``
  and ``W`` must equal the goldens bit for bit.  It runs twice, in two
  children: the second start finds the first one's programs in the
  persistent compile cache (``JAX_COMPILATION_CACHE_DIR`` if set, else
  ``<checkout>/.jax_cache``) and shows a lower cold time.
* ``real`` — a ``fintxn:`` tenant of at least 2^22 temporal edges:
  fixed-budget M5-3 and M4-2 requests at k = 2^20, a repeat of the first
  (warm, and bit-identical to it), and an M5-3 request with
  ``target_rse`` 0.05 that must end at or under it.

``--four-chips`` runs only the mesh check on the real-size tenant: its
fixed-budget M4-2 request at k = 2^20, first on a server started with
``--mesh 4`` and then, after that child has exited, on one with no mesh.
``estimate``, ``valid`` and ``W`` must be bit-identical between the two
(the engine's determinism contract), and the sharded server must report
4 devices and a 4-way mesh.  M4-2 alone keeps each child to two
candidate weight DPs, which the mesh does not shard (ROADMAP S5, S8).

Every response must be ``ok``, sampled by the ``xla`` backend, with no
``fallback_reason`` and not ``degraded``.  The lines before the last
describe each phase — graph size, cold time (compiles included), warm
time, device peak memory — as one smoke run, not a benchmark.  The last
line, printed only when every phase passed, is ``{"ok": true, "device":
{...}}`` from the server's ``health`` reply; any failure exits non-zero.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import queue
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")
GOLDEN = os.path.join(REPO, "tests", "golden_estimates.json")

#: the real-size tenant: 4,352,338 temporal edges over 280,568 accounts
#: (power-law transfers with planted laundering rings, seeded)
REAL_GRAPH = ("fintxn:n_accounts=524288,m=4500000,time_span=16000000,"
              "n_rings=32000,seed=5")
REAL_DELTA = 2_000
REAL_K = 1 << 20
#: per-child wall-clock deadlines, seconds (compiles included); every
#: child also ends by the whole run's deadline
SMALL_DEADLINE_S = 420.0
REAL_DEADLINE_S = 1000.0
FOUR_CHIP_DEADLINE_S = 330.0
RUN_DEADLINE_S = 1140.0
T_START = time.monotonic()


class SmokeError(RuntimeError):
    """A phase failed: the script reports it and exits non-zero."""


def progress(msg: str) -> None:
    """One timestamped line on stderr, so a failed run shows how far each
    phase got and when."""
    print(f"chip_smoke [{time.monotonic() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


class Server:
    """One gateway server child, spoken to in NDJSON under a deadline."""

    def __init__(self, flags: list, deadline_s: float, label: str):
        self.label = label
        progress(f"{label}: starting a server {' '.join(flags)}")
        env = dict(os.environ, JAX_PLATFORMS="tpu")
        # per-stage latency histograms for the phase lines (never
        # result-affecting: estimates are bit-identical at every level)
        env.setdefault("REPRO_OBS", "metrics")
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (SRC, os.environ.get("PYTHONPATH")) if p)
        self.deadline = min(time.monotonic() + deadline_s,
                            T_START + RUN_DEADLINE_S)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "repro.launch.estimate", "--serve",
             "--gateway", *flags],
            cwd=REPO, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, bufsize=1)
        self.lines: queue.Queue = queue.Queue()
        self.stderr: collections.deque = collections.deque(maxlen=400)
        self._readers = [
            threading.Thread(target=self._pump, args=(self.proc.stdout,
                                                      self.lines.put),
                             daemon=True),
            threading.Thread(target=self._pump, args=(self.proc.stderr,
                                                      self.stderr.append),
                             daemon=True)]
        for t in self._readers:
            t.start()

    @staticmethod
    def _pump(stream, put) -> None:
        for line in stream:
            put(line.rstrip("\n"))
        put(None)

    def _fail(self, why: str):
        tail = "\n".join(ln for ln in list(self.stderr)[-40:] if ln)
        raise SmokeError(f"{self.label}: {why}\n--- server stderr (tail) ---"
                         f"\n{tail}")

    def call(self, obj: dict, match) -> tuple:
        """Send one line; return ``(reply, seconds)`` for the first reply
        that ``match`` accepts.  A reply with ``ok: false`` fails."""
        t0 = time.monotonic()
        try:
            self.proc.stdin.write(json.dumps(obj) + "\n")
            self.proc.stdin.flush()
        except OSError as e:
            self._fail(f"server gone before {obj}: {e}")
        while True:
            left = self.deadline - time.monotonic()
            if left <= 0:
                self._fail(f"deadline passed waiting for the reply to {obj}")
            try:
                line = self.lines.get(timeout=left)
            except queue.Empty:
                continue
            if line is None:
                self.proc.wait(timeout=30)
                self._fail(f"server exited (rc={self.proc.returncode}) "
                           f"before answering {obj}")
            reply = json.loads(line)
            if match(reply):
                if reply.get("ok") is not True:
                    self._fail(f"{obj} answered {reply}")
                dt = time.monotonic() - t0
                progress(f"{self.label}: {obj} answered in {dt:.2f}s")
                return reply, dt

    def health(self) -> tuple:
        return self.call({"cmd": "health"},
                         lambda r: r.get("cmd") == "health")

    def stages(self) -> str:
        """Seconds and count per serving stage so far, from the server's
        ``repro_stage_seconds`` histograms (the ``metrics`` verb)."""
        r, _ = self.call({"cmd": "metrics"},
                         lambda r: r.get("cmd") == "metrics")
        tot: dict = {}
        for line in r["text"].splitlines():
            for part in ("_sum", "_count"):
                head = f"repro_stage_seconds{part}{{stage=\""
                if line.startswith(head):
                    stage = line[len(head):].split('"', 1)[0]
                    tot.setdefault(stage, {})[part] = float(line.split()[-1])
        return " ".join(
            f"{st}={v.get('_sum', 0):.2f}s/{v.get('_count', 0):.0f}"
            for st, v in sorted(tot.items()))

    def request(self, tenant: str, rid: int, **fields) -> tuple:
        r, dt = self.call(dict(tenant=tenant, id=rid, **fields),
                          lambda r: r.get("id") == rid
                          and not r.get("progress"))
        if (r["sampler_backend"] != "xla" or r["fallback_reason"]
                or r.get("degraded")):
            self._fail(f"request {rid} left the plain xla path: {r}")
        return r, dt

    def close(self) -> None:
        """``quit``, then the child must exit 0 within its deadline."""
        self.call({"cmd": "quit"}, lambda r: r.get("cmd") == "quit")
        self.proc.stdin.close()
        try:
            rc = self.proc.wait(timeout=max(1.0, self.deadline
                                            - time.monotonic()))
        except subprocess.TimeoutExpired:
            self._fail("server did not exit after quit")
        if rc != 0:
            self._fail(f"server exited with code {rc}")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


def _serve(flags: list, deadline_s: float, label: str, body):
    """Run ``body(server)`` against one child; the child is gone after."""
    srv = Server(flags, deadline_s, label)
    try:
        out = body(srv)
        srv.close()
        return out
    finally:
        srv.kill()


def _mib(b) -> str:
    return "not reported" if b is None else f"{b / 2**20:.1f} MiB"


def _check_tpu(device: dict, label: str, count: int) -> None:
    if device["platform"] != "tpu" or device["count"] != count:
        raise SmokeError(f"{label}: server computes on {device}, want "
                         f"{count} tpu device(s)")


def _golden() -> tuple:
    """The golden file and the server flags its requests were made with."""
    with open(GOLDEN) as f:
        golden = json.load(f)
    return golden, ["--chunk", str(golden["chunk"]),
                    "--checkpoint-every", str(golden["checkpoint_every"])]


def _answer(r: dict) -> dict:
    return {f: r[f] for f in ("estimate", "valid", "W")}


def small_phase(run: int) -> dict:
    """The golden graph and requests, bit for bit."""
    golden, flags = _golden()

    def body(srv):
        t_start = time.monotonic()
        srv.health()
        started = time.monotonic() - t_start
        opened, t_open = srv.call(
            {"cmd": "open_tenant", "tenant": "golden",
             "graph": golden["graph"]},
            lambda r: r.get("cmd") == "open_tenant")
        cold = []
        for i, g in enumerate(golden["requests"]):
            r, dt = srv.request("golden", i, motif=g["motif"],
                                delta=g["delta"], k=g["k"], seed=g["seed"])
            got, want = _answer(r), _answer(g)
            if got != want:
                raise SmokeError(f"small#{run}: {g['motif']} answered "
                                 f"{got}, golden {want}")
            cold.append((g["motif"], dt))
        g = golden["requests"][0]
        r, warm = srv.request("golden", 99, motif=g["motif"],
                              delta=g["delta"], k=g["k"], seed=g["seed"])
        if r["estimate"] != g["estimate"]:
            raise SmokeError(f"small#{run}: warm repeat answered {r}")
        h, _ = srv.health()
        _check_tpu(h["device"], f"small#{run}", 1)
        print(f"phase small#{run}: n={opened['n']} m={opened['m']} "
              f"start={started:.2f}s open={t_open:.2f}s cold "
              + " ".join(f"{mn}={dt:.2f}s" for mn, dt in cold)
              + f" warm={warm:.3f}s peak={_mib(h['device']['peak_bytes'])}"
              f" goldens=bit-identical (one smoke run, not a benchmark)",
              flush=True)
        return h["device"]

    return _serve(flags, SMALL_DEADLINE_S, f"small#{run}", body)


def real_phase() -> dict:
    def body(srv):
        srv.health()
        opened, t_open = srv.call(
            {"cmd": "open_tenant", "tenant": "fin", "graph": REAL_GRAPH},
            lambda r: r.get("cmd") == "open_tenant")
        if opened["m"] < 1 << 22:
            raise SmokeError(f"real: real-size tenant has m={opened['m']}")
        answers, cold = {}, {}
        for i, mn in enumerate(("M5-3", "M4-2")):
            r, cold[mn] = srv.request("fin", i, motif=mn, delta=REAL_DELTA,
                                      k=REAL_K, seed=0)
            answers[mn] = _answer(r)
        r, warm = srv.request("fin", 2, motif="M5-3", delta=REAL_DELTA,
                              k=REAL_K, seed=0)
        if _answer(r) != answers["M5-3"]:
            raise SmokeError(f"real: repeat of M5-3 answered {r}, first "
                             f"{answers['M5-3']}")
        r, t_rse = srv.request("fin", 3, motif="M5-3", delta=REAL_DELTA,
                               k=REAL_K // 2, seed=1, target_rse=0.05,
                               k_max=REAL_K * 4)
        if r["rse"] is None or r["rse"] > 0.05:
            raise SmokeError(f"real: target_rse request ended at {r}")
        h, _ = srv.health()
        _check_tpu(h["device"], "real", 1)
        print(f"phase real: n={opened['n']} m={opened['m']} "
              f"open={t_open:.2f}s cold "
              + " ".join(f"{mn}={dt:.2f}s" for mn, dt in cold.items())
              + f" warm M5-3={warm:.3f}s target_rse: k={r['k']} "
              f"rse={r['rse']:.4f} in {t_rse:.2f}s "
              f"peak={_mib(h['device']['peak_bytes'])} "
              f"(one smoke run, not a benchmark)", flush=True)
        for mn, a in answers.items():
            print(f"  {mn} k={REAL_K}: {a}", flush=True)
        print(f"  stages (seconds/count): {srv.stages()}", flush=True)
        return h["device"]

    return _serve([], REAL_DEADLINE_S, "real", body)


def four_chip_phase() -> dict:
    """The real-size tenant's fixed-budget M4-2 request on a 4-way mesh,
    then unsharded: bit-identical."""
    req = dict(motif="M4-2", delta=REAL_DELTA, k=REAL_K, seed=0)

    def run(extra, label):
        def body(srv):
            h, _ = srv.health()
            _check_tpu(h["device"], label, 4)
            opened, t_open = srv.call(
                {"cmd": "open_tenant", "tenant": "fin", "graph": REAL_GRAPH},
                lambda r: r.get("cmd") == "open_tenant")
            r, dt = srv.request("fin", 0, **req)
            h, _ = srv.health()
            print(f"phase {label}: n={opened['n']} m={opened['m']} "
                  f"open={t_open:.2f}s cold M4-2={dt:.2f}s "
                  f"peak={_mib(h['device']['peak_bytes'])} "
                  f"(one smoke run, not a benchmark)", flush=True)
            print(f"  stages (seconds/count): {srv.stages()}", flush=True)
            banner = next((ln for ln in srv.stderr
                           if ln.startswith("serving GATEWAY")), "")
            return h["device"], _answer(r), banner
        return _serve(extra, FOUR_CHIP_DEADLINE_S, label, body)

    device, sharded, banner = run(["--mesh", "4"], "mesh4")
    if "mesh={'data': 4}" not in banner:
        raise SmokeError(f"mesh4: banner shows no 4-way mesh: {banner!r}")
    _, plain, _ = run([], "nomesh")
    if sharded != plain:
        raise SmokeError(f"mesh4 answered {sharded}, unsharded {plain}")
    print(f"  banner: {banner}", flush=True)
    print(f"  M4-2 k={REAL_K} seed=0: {sharded} (mesh4 == nomesh, "
          f"bit-identical)", flush=True)
    return device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-way mesh phase (needs 4 chips)")
    args = ap.parse_args()
    if not (os.path.isdir(os.path.join(SRC, "repro"))
            and os.path.isfile(GOLDEN)):
        print(f"chip_smoke: no repro checkout around {REPO}",
              file=sys.stderr)
        return 2
    try:
        if args.four_chips:
            device = four_chip_phase()
        else:
            small_phase(1)
            small_phase(2)
            device = real_phase()
    except (SmokeError, OSError, ValueError, KeyError) as e:
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
