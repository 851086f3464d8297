"""The bytes-per-sample lower bound on a known tree and known draws."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import reference as R
from bench import workmodel


def _draw(listed):
    spans = {"windows": 7, "root": np.array([15, 15]),
             "child": [np.array([3, 3]), np.array([7, 7])],
             "pair": [], "listed": listed}
    return SimpleNamespace(W=2**20 - 1, spans=spans)


def test_star_tree_bytes():
    star = R.rooted_tree(((0, 1), (0, 2), (0, 3)), (0, 1, 2), 1)
    g = SimpleNamespace(n=256, span=1023)
    # records 3 x (8 + 8 + 10) bits; prefix entries (3 + 4 + 2 + 3) x 20
    assert workmodel.bytes_per_sample(_draw([]), g, star) == \
        pytest.approx((78 + 240) / 8)


def test_completion_lists_add_their_times():
    cyc = R.rooted_tree(((0, 1), (1, 2), (2, 0)), (0, 1), 0)
    g = SimpleNamespace(n=256, span=1023)
    got = workmodel.bytes_per_sample(_draw([np.array([2, 4])]), g, cyc)
    # two tree edges: records 2 x 26; prefix 12 x 20; lists 3 x 10
    assert got == pytest.approx((52 + 240 + 30) / 8)


@pytest.mark.parametrize("x,b", [(0, 1), (1, 1), (2, 2), (255, 8),
                                 (256, 9)])
def test_bits(x, b):
    assert workmodel.bits(x) == b
