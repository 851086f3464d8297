"""The one traffic generator: closed-loop clients from a mix's parameters.

A mix (``bench/traffic/<mix>.json``) states:

* ``clients``: closed-loop clients; each sends its next request when the
  last one is answered;
* ``k``: the fixed sample budget of every request;
* ``server``: the gateway's ``chunk`` and ``checkpoint_every``;
* ``profile_windows``: engine windows the traced run captures.

Requests cycle through the configuration's standing (motif, delta)
pairs; each carries a fresh seed derived from the run's seed.
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


class Requests:
    """The deterministic request sequence of each client."""

    def __init__(self, mix: dict, standing: list, seed: int):
        self.mix, self.standing, self.seed = mix, standing, int(seed)

    def request(self, client: int, i: int) -> dict:
        motif, delta = self.standing[(client + i) % len(self.standing)]
        return dict(id=f"c{client}.{i}", motif=motif, delta=int(delta),
                    k=int(self.mix["k"]), seed=_mix(self.seed, 0, client, i))


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
