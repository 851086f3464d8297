"""The one traffic generator: closed-loop clients from a mix's parameters.

A mix (``bench/traffic/<mix>.json``) states:

* ``clients``: closed-loop clients; each sends its next request when the
  last one is answered;
* ``k``: the fixed sample budget of every request;
* ``target_rse`` (optional, a list) and ``k_max``: screen traffic.  Every
  request then states a target relative error and a budget cap, and
  ``k`` is its initial budget, which the server grows until the target
  is met or ``k_max`` is reached;
* ``server``: the gateway's ``chunk`` and ``checkpoint_every``;
* ``profile_windows``: engine windows the traced run captures.

Requests cycle through the configuration's standing (motif, delta)
pairs, and with ``target_rse`` through every (pair, target) combination,
the motif varying first; each carries a fresh seed derived from the
run's seed.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

import numpy as np


def _mix(seed: int, *tags: int) -> int:
    """A request seed in [0, 2^31) from the run's seed and its position."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), *tags])
    return int(ss.generate_state(1)[0] >> 1)


def window_samples(mix: dict) -> int:
    """Samples in one engine window: ``chunk * checkpoint_every``."""
    return int(mix["server"]["chunk"]) * int(mix["server"]["checkpoint_every"])


def stated(motif: str, delta: int, k: int, seed: int, target,
           mix: dict) -> dict:
    """One request's fields; a stated error adds ``target_rse`` and the
    mix's ``k_max``."""
    req = dict(motif=motif, delta=int(delta), k=int(k), seed=seed)
    if target is not None:
        req.update(target_rse=float(target), k_max=int(mix["k_max"]))
    return req


class Requests:
    """The deterministic request sequence of each client."""

    def __init__(self, mix: dict, standing: list, seed: int):
        self.mix, self.seed = mix, int(seed)
        # (motif, delta, target), the motif varying first; no target
        # where the mix states none
        self.combos = [(motif, int(delta), t)
                       for t in mix.get("target_rse") or [None]
                       for motif, delta in standing]

    def request(self, client: int, i: int) -> dict:
        motif, delta, target = self.combos[(client + i) % len(self.combos)]
        return dict(id=f"c{client}.{i}",
                    **stated(motif, delta, self.mix["k"],
                             _mix(self.seed, 0, client, i), target,
                             self.mix))


@dataclass
class Record:
    """One request of the window, as the client saw it."""
    req: dict
    sent: float
    answered: float | None = None
    reply: dict | None = None

    @property
    def ok(self) -> bool:
        r = self.reply
        return bool(r and r.get("ok") is True and not r.get("degraded"))

    @property
    def met(self) -> bool:
        """Answered, and within its stated error where it states one:
        the ``rse`` it reports is at most its ``target_rse``."""
        target = self.req.get("target_rse")
        rse = self.reply.get("rse") if self.ok else None
        return self.ok and (target is None
                            or (rse is not None and rse <= target))


@dataclass
class Window:
    t0: float
    t1: float
    records: list = field(default_factory=list)
    late: float = 0.0            # worst delay of a client's next send


def run_closed_loop(server, tenant: str, reqs: Requests, clients: int,
                    seconds: float, grace: float = 60.0) -> Window:
    """Drive ``clients`` closed-loop clients for ``seconds``; requests
    still out at the close are awaited up to ``grace`` seconds more."""
    lock = threading.Lock()
    win = Window(t0=time.monotonic(), t1=0.0)
    win.t1 = win.t0 + seconds

    def client(c: int) -> None:
        i = 0
        while True:
            now = time.monotonic()
            if now >= win.t1:
                return
            req = reqs.request(c, i)
            rec = Record(req=req, sent=server.send(dict(req, tenant=tenant)))
            with lock:
                win.records.append(rec)
                win.late = max(win.late, rec.sent - now)
            try:
                rec.answered, rec.reply = server.wait(("id", req["id"]),
                                                      until=win.t1 + grace)
            except TimeoutError:
                return
            i += 1

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + grace + 30)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a client thread did not finish")
    return win


def in_window_share(rec: Record, t0: float, t1: float) -> float:
    """The share of a request's send-to-answer time that lies inside the
    window: requests straddling an edge count pro rata."""
    if rec.answered is None:
        return 0.0
    dur = rec.answered - rec.sent
    if dur <= 0:
        return 1.0 if t0 <= rec.sent <= t1 else 0.0
    inside = min(rec.answered, t1) - max(rec.sent, t0)
    return max(0.0, inside) / dur
