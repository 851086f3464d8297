"""Pro-rata window accounting and the host-clock metric readers."""
from types import SimpleNamespace

import pytest

from bench import traffic
from bench.run import reader
from bench.traffic import Record, Window, in_window_share


def _rec(sent, answered, k=100, ok=True):
    reply = {"ok": ok, "k": k}
    return Record(req={"k": k}, sent=sent, answered=answered, reply=reply)


@pytest.mark.parametrize("sent,answered,share", [
    (1.0, 2.0, 1.0),        # inside
    (-1.0, 1.0, 0.5),       # straddles the opening
    (9.0, 12.0, 1 / 3),     # straddles the close
    (-2.0, 12.0, 10 / 14),  # spans the whole window
    (11.0, 12.0, 0.0),      # after it
])
def test_in_window_share(sent, answered, share):
    assert in_window_share(_rec(sent, answered), 0.0, 10.0) == \
        pytest.approx(share)


def test_unanswered_request_counts_nothing():
    rec = Record(req={"k": 1}, sent=1.0)
    assert in_window_share(rec, 0.0, 10.0) == 0.0


def _ctx(records, seconds=10.0):
    w = Window(t0=0.0, t1=seconds, records=records)
    return SimpleNamespace(window=w, seconds=seconds)


def test_samples_per_s_pro_rata():
    recs = [_rec(0.0, 5.0, k=1000), _rec(5.0, 10.0, k=1000),
            _rec(10.0 - 1.0, 10.0 + 3.0, k=4000),
            _rec(2.0, 3.0, k=10**6, ok=False)]
    assert reader("samples_per_s")(_ctx(recs)) == pytest.approx(
        (1000 + 1000 + 1000) / 10.0)


def test_request_sequence_is_seeded():
    mix = {"clients": 2, "k": 16384,
           "server": {"chunk": 4096, "checkpoint_every": 2}}
    standing = [["M4-2", 3600], ["M5-3", 2000]]
    a = traffic.Requests(mix, standing, 2**31 + 5)
    b = traffic.Requests(mix, standing, 2**31 + 5)
    c = traffic.Requests(mix, standing, 7)
    first = [a.request(1, i) for i in range(10)]
    assert first == [b.request(1, i) for i in range(10)]
    assert first != [c.request(1, i) for i in range(10)]
    assert {r["k"] for r in first} == {16384}
    assert [r["motif"] for r in first[:2]] == ["M5-3", "M4-2"]
    assert [r["delta"] for r in first[:2]] == [2000, 3600]
    assert all(0 <= r["seed"] < 2**31 for r in first)
    assert len({r["seed"] for r in first}) == 10
