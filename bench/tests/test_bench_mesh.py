"""A cell with ``chips: 4`` on four virtual CPU devices, through the real
gateway at the tiny size: the server is started with ``--mesh 4``, the
result names the mesh, and the answers are the one-device run's, bit for
bit (the engine's determinism contract).  A run that asks for more chips
than the server sees ends with ``BenchError`` and no result, and a mesh
whose windows leave out the exchange between chips comes out not
correct."""
import dataclasses
import json
import os

import pytest

from bench.run import BenchError
from bench.tests.test_bench_rehearsal import _cell, _run

SEED = 717171
#: four virtual CPU devices for the child, which copies this environment
FOUR = "--xla_force_host_platform_device_count=4"
#: loaded by the child's interpreter at start-up: every ``psum`` returns
#: its own shard, so a window's sums are one device's share
NO_PSUM = "import jax\njax.lax.psum = lambda x, *a, **k: x\n"


def _answers(out_dir) -> dict:
    with open(os.path.join(out_dir, "answers.json")) as f:
        return {a["id"]: (a["W"], a["estimate"]) for a in json.load(f)
                if not a["failed"]}


def _mesh_run(out_dir, cache, chips, shim=None):
    """A run of the tiny cell asking for ``chips`` chips, the child seeing
    four devices (and loading ``shim`` as its ``sitecustomize``)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("XLA_FLAGS", " ".join(
            f for f in (os.environ.get("XLA_FLAGS"), FOUR) if f))
        if shim is not None:
            shim_dir = os.path.join(out_dir, "shim")
            os.makedirs(shim_dir)
            with open(os.path.join(shim_dir, "sitecustomize.py"), "w") as f:
                f.write(shim)
            mp.setenv("PYTHONPATH", os.pathsep.join(
                p for p in (shim_dir, os.environ.get("PYTHONPATH")) if p))
        cell = dataclasses.replace(_cell(), chips=chips)
        return _run(os.path.join(out_dir, "run"), cache, seed=SEED,
                    cell=cell)


def test_four_chip_cell_serves_on_a_mesh_with_the_same_answers(tmp_path,
                                                               jax_cache):
    one = _run(tmp_path / "one", jax_cache, seed=SEED)
    four = _mesh_run(str(tmp_path / "four"), jax_cache, 4)
    assert one["correct"] and four["correct"], (one["check"], four["check"])
    assert one["device"]["mesh"] is None
    assert four["device"]["mesh"] == {"data": 4}
    assert four["device"]["count"] == 4
    a1 = _answers(tmp_path / "one")
    a4 = _answers(os.path.join(tmp_path, "four", "run"))
    common = set(a1) & set(a4)
    assert common
    assert {i: a4[i] for i in common} == {i: a1[i] for i in common}


def test_more_chips_than_the_server_sees_gives_no_result(tmp_path,
                                                         jax_cache):
    with pytest.raises(BenchError):
        _mesh_run(str(tmp_path), jax_cache, 8)


def test_exchange_left_out_is_not_correct(tmp_path, jax_cache):
    r = _mesh_run(str(tmp_path), jax_cache, 4, shim=NO_PSUM)
    assert r["device"]["mesh"] == {"data": 4}
    assert not r["correct"]
    c = r["check"]["z_max"]
    assert c["value"] > c["limit"], r["check"]
