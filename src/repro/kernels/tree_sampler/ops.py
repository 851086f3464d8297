"""Dispatch seam for the fused Pallas sampler (REPRO_SAMPLER_BACKEND=pallas).

``make_pallas_sample_fn(tree, K)`` returns a jitted drop-in for
``core.sampler.make_sample_fn``'s XLA path: same ``fn(dev, wts, key) ->
{edges, window, phi_v}`` signature, bit-identical samples.  Randomness is
prepared on the XLA side (``prepare_draws``) so the kernel itself is
deterministic; ``pallas_sampler_eligible`` is the host-side gate callers
use to fall back to XLA outside the kernel's exactness/capacity envelope:

* every weight prefix top must sit inside f32's exact-integer range
  (< 2^24) — beyond it the f32 bisection comparisons would round;
* window-shifted time bounds must fit int32;
* the kernel-resident structure must fit the VMEM budget
  (``REPRO_SAMPLER_VMEM_MB``, default 192 — sized for interpret mode; a
  v5e's compiler caps VMEM at 128 MiB).

CPU-interpret only until ROADMAP S2: off a TPU ``interpret`` defaults to
True, and on a TPU the compiler refuses the kernel (Mosaic lowering of
its first bisection fails under x64), so no chip has run it.

Structural-fields-only contract: this module (like the XLA sampler it
mirrors) reads ONLY the fields captured by
``core.spanning_tree.tree_signature`` — ``num_vertices``, ``root``,
``parent``, ``deps``, ``topo_down``, ``vertex_source`` and the derived
``num_edges`` — never ``edge_ids`` or non-tree motif edges.  That is
what lets the engine's tree-cohorts share ONE sample stream across
signature-equal trees: two trees with equal signatures drive this
sampler to bit-identical draws, so any motif in the cohort may score
the shared stream with its own count lane.  Adding a read of a
non-signature field here would silently break cohort bit-identity —
extend ``tree_signature`` in the same change.
"""
from __future__ import annotations

from ...util import ensure_x64

ensure_x64()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ...core.bisect import converge_iters  # noqa: E402
from ...core.sampler import bisect_iters  # noqa: E402
from ...knobs import get_knob  # noqa: E402
from ...core.spanning_tree import SpanningTree  # noqa: E402
from .kernel import build_schedule, tree_sampler_call  # noqa: E402

_F32_EXACT_MAX = 1 << 24
_I32 = jnp.int32
_F32 = jnp.float32


def prepare_draws(tree: SpanningTree, wts, key, K: int):
    """All randomness for K samples, on the XLA side.

    Mirrors the XLA sampler's key schedule exactly: ``keys[0]`` yields the
    int64 window/center target ``x`` (its span ``W`` is known here), and
    each child edge ``c`` gets the two raw 64-bit draws that
    ``jax.random.randint(keys[2+c], ...)`` would split off internally —
    the kernel replays the modular reduction against the data-dependent
    span (``kernel.randint_from_bits``).  Returns ``(x [K] i64,
    uhi [K, S] u64, ulo [K, S] u64)``.
    """
    S = tree.num_edges
    keys = jax.random.split(key, S + 2)
    W = jnp.maximum(wts.W_total, 1)
    x = jax.random.randint(keys[0], (K,), 0, W, dtype=jnp.int64)
    zeros = jnp.zeros((K,), jnp.uint64)
    his, los = [], []
    for c in range(S):
        if c == tree.root:
            his.append(zeros)
            los.append(zeros)
        else:
            k1, k2 = jax.random.split(keys[2 + c])
            his.append(jax.random.bits(k1, (K,), jnp.uint64))
            los.append(jax.random.bits(k2, (K,), jnp.uint64))
    return x, jnp.stack(his, axis=1), jnp.stack(los, axis=1)


def _device_prep(dev, wts):
    """Kernel-resident structure: i32 indices/times, f32 prefix sums."""
    return dict(
        t=dev["t"].astype(_I32),
        src=dev["src"].astype(_I32),
        dst=dev["dst"].astype(_I32),
        out_ptr=dev["out_ptr"].astype(_I32),
        in_ptr=dev["in_ptr"].astype(_I32),
        out_t=dev["out_t"].astype(_I32),
        in_t=dev["in_t"].astype(_I32),
        out_edge=dev["out_edge"].astype(_I32),
        in_edge=dev["in_edge"].astype(_I32),
        pair_pos_out=dev["pair_pos_out"].astype(_I32),
        pair_pos_in=dev["pair_pos_in"].astype(_I32),
        pair_ptr=dev["pair_ptr"].astype(_I32),
        pair_t=dev["pair_t"].astype(_I32),
        pair_id=dev["pair_id"].astype(_I32),
        rev_pair_id=dev["rev_pair_id"].astype(_I32),
        ps_win=wts.ps_win.astype(_F32),
        win_lo=wts.win_lo.astype(_I32),
        win_mid=wts.win_mid.astype(_I32),
        win_hi=wts.win_hi.astype(_I32),
        ps_acc_own=wts.ps_acc_own.astype(_F32),
        ps_acc_prev=wts.ps_acc_prev.astype(_F32),
        ps_pair_own=wts.ps_pair_own.astype(_F32),
        ps_pair_prev=wts.ps_pair_prev.astype(_F32),
    )


def kernel_vmem_bytes(m: int, n: int, P: int, q: int, S: int) -> int:
    """Bytes of kernel-resident structure (excl. the streamed sample block)."""
    i32_edge_arrays = 12 * m * 4          # times/ids/positions, both CSRs
    ptrs = (2 * (n + 1) + (P + 1)) * 4
    prefixes = 4 * S * (m + 1) * 4        # ps_acc_* + ps_pair_*, f32
    windows = (4 * q + 1) * 4
    return i32_edge_arrays + ptrs + prefixes + windows


def pallas_sampler_eligible(dev, wts, *, vmem_budget_bytes: int | None = None
                            ) -> tuple[bool, str]:
    """Host-side gate for the fused sampler; (ok, reason).

    Must be called with concrete (non-traced) ``dev``/``wts`` — it pulls a
    few scalars to the host.  ``estimate()`` runs it once per job.
    """
    top = int(jnp.maximum(
        jnp.max(jnp.stack([
            jnp.max(wts.ps_acc_own[:, -1]), jnp.max(wts.ps_acc_prev[:, -1]),
            jnp.max(wts.ps_pair_own[:, -1]),
            jnp.max(wts.ps_pair_prev[:, -1])])),
        wts.ps_win[-1]))
    if top >= _F32_EXACT_MAX:
        return False, (f"weight prefix {top} outside f32-exact range 2^24; "
                       "xla int64 path required")
    tmax = int(dev["t"][-1])
    if tmax + 2 * max(int(wts.delta), int(wts.wd)) >= 2 ** 31:
        return False, "window-shifted time bounds exceed int32"
    m = int(dev["t"].shape[0])
    n = int(dev["out_ptr"].shape[0]) - 1
    P = int(dev["pair_ptr"].shape[0]) - 1
    need = kernel_vmem_bytes(m, n, P, wts.q_pad, wts.tree.num_edges)
    budget = (vmem_budget_bytes if vmem_budget_bytes is not None
              else get_knob("REPRO_SAMPLER_VMEM_MB") << 20)
    if need > budget:
        return False, (f"kernel-resident structure {need} B exceeds VMEM "
                       f"budget {budget} B (REPRO_SAMPLER_VMEM_MB)")
    return True, "ok"


def make_pallas_sample_fn(tree: SpanningTree, K: int, *, bk: int | None = None,
                          interpret: bool | None = None):
    """Jitted fused-sampler twin of ``core.sampler.make_sample_fn``.

    One ``pallas_call`` executes the whole per-sample pipeline; only the
    draw preparation and the final ``phi_v`` vertex-map gathers stay in
    XLA.  Callers must gate with ``pallas_sampler_eligible`` (results are
    silently wrong past the f32-exact weight range).
    """
    S = tree.num_edges
    nv = tree.motif.num_vertices
    root = tree.root
    schedule = build_schedule(tree)
    if bk is None:
        bk = get_knob("REPRO_SAMPLER_BLOCK")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def fn(dev, wts, key):
        # the XLA sampler's top-level scope: a trace names both alike
        with jax.named_scope("sample"):
            return draw(dev, wts, key)

    def draw(dev, wts, key):
        m = dev["t"].shape[0]
        it = bisect_iters(m)
        # static shape-derived trip count (wts.q is traced); == the old
        # q-derived count on unpadded graphs
        itq = converge_iters(wts.q_pad)
        x, uhi, ulo = prepare_draws(tree, wts, key, K)
        arrays = _device_prep(dev, wts)
        edges32, win32 = tree_sampler_call(
            arrays, x.astype(_I32), uhi, ulo, root=root, schedule=schedule,
            use_c2=wts.use_c2, it=it, itq=itq, delta=int(wts.delta),
            wd=int(wts.wd), S=S, bk=bk, interpret=interpret)
        E = edges32.astype(jnp.int64)
        win = win32.astype(jnp.int64)
        cols = []
        for vtx in range(nv):
            s_loc, end = tree.vertex_source[vtx]
            arr = dev["src"] if end == 0 else dev["dst"]
            cols.append(arr[E[:, s_loc]].astype(jnp.int64))
        phi_v = jnp.stack(cols, axis=1)
        return dict(edges=E, window=win, phi_v=phi_v)

    return jax.jit(fn)
