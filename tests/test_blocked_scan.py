"""The shared blocked-scan core: the one ``scan(carry, slab) -> carry``
contract every neighbors engine routes through.

Pins, at the core level (engine-level parity lives in test_probe_block /
test_cagra_frontier / test_neighbors):

* **bit-invariance across block sizes** — ``slab_dots`` keeps the block
  axis in the einsum's *batch* dims, so scores (and therefore scan
  results, values AND ids) are bit-identical however the candidate stream
  is blocked;
* **payload lanes** — ``fold_topk_payload`` selects the same (value, id)
  set as the payload-free fold and gathers payloads through the same
  winning positions;
* **filter-mask compose** — +inf'd lanes never surface, a fully-masked
  block is a no-op on the carry;
* **dispatch gate** — the platform alone picks Mosaic or interpret
  mode;
* **steady state** — alternating warm scan specializations neither
  re-traces nor transfers.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.core import TraceGuard
from raft_tpu.ops import blocked_scan as bs
from raft_tpu.ops.pallas import gate as gate_mod

NQ, D, K = 8, 24, 10


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(11)
    data = rng.standard_normal((1536, D)).astype(np.float32)
    q = rng.standard_normal((NQ, D)).astype(np.float32)
    return jnp.asarray(data), jnp.asarray(q)


def _reference_topk(data, q, k):
    """lax.top_k over the SAME slab_dots scoring (one whole-corpus slab):
    the scan must reproduce a direct full-matrix selection bit-for-bit."""
    n = data.shape[0]
    vid = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (q.shape[0], n))
    dots = bs.slab_dots(data[vid][:, None], q).reshape(q.shape[0], n)
    dist = jnp.sum(data.astype(jnp.float32) ** 2, axis=1)[vid] - 2.0 * dots
    neg, idx = jax.lax.top_k(-dist, k)
    return np.asarray(-neg), np.asarray(idx)


def _scan_over_blocks(data, q, n_blocks, k):
    """scan_topk over the corpus split into ``n_blocks`` slabs, scored
    through slab_dots with the block dim pinned (B = 1 per step here; the
    B-axis invariance is pinned separately below)."""
    n = data.shape[0]
    c = n // n_blocks
    norms = jnp.sum(data.astype(jnp.float32) ** 2, axis=1)
    xs = jnp.arange(n_blocks, dtype=jnp.int32)
    lane = jnp.arange(c, dtype=jnp.int32)

    def score(blk):
        vid = jnp.broadcast_to(blk * c + lane, (q.shape[0], c))
        dots = bs.slab_dots(data[vid][:, None], q)
        return norms[vid] - 2.0 * dots.reshape(q.shape[0], c), vid

    return bs.scan_topk(score, xs, q.shape[0], k)


# ---------------------------------------------------------------------------
# bit-invariance


def test_scan_topk_matches_reference(corpus):
    data, q = corpus
    rv, ri = _reference_topk(data, q, K)
    gv, gi = _scan_over_blocks(data, q, 1, K)
    np.testing.assert_array_equal(np.asarray(gv), rv)
    np.testing.assert_array_equal(np.asarray(gi), ri)


def test_scan_topk_bit_invariant_across_block_counts(corpus):
    data, q = corpus
    ref = _scan_over_blocks(data, q, 1, K)
    for n_blocks in (2, 4, 12):
        gv, gi = _scan_over_blocks(data, q, n_blocks, K)
        np.testing.assert_array_equal(np.asarray(gv), np.asarray(ref[0]),
                                      err_msg=f"n_blocks={n_blocks}")
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(ref[1]),
                                      err_msg=f"n_blocks={n_blocks}")


def test_slab_dots_pins_block_axis(corpus):
    """Scoring a [nq, B, C, d] slab must equal B separate [nq, 1, C, d]
    scorings bit-for-bit — the accumulation-shape contract that makes
    every block size produce identical distance bits."""
    data, q = corpus
    b, c = 4, 96
    slab = data[: b * c].reshape(1, b, c, D)
    slab = jnp.broadcast_to(slab, (NQ, b, c, D))
    whole = bs.slab_dots(slab, q)
    for j in range(b):
        part = bs.slab_dots(slab[:, j:j + 1], q)
        np.testing.assert_array_equal(np.asarray(whole[:, j]),
                                      np.asarray(part[:, 0]))


# ---------------------------------------------------------------------------
# folds: payload lanes, masks, carry


def test_fold_topk_payload_matches_plain_fold(corpus):
    data, q = corpus
    rng = np.random.default_rng(3)
    bv = jnp.asarray(rng.standard_normal((NQ, K)).astype(np.float32))
    bi = jnp.asarray(rng.integers(0, 500, (NQ, K)).astype(np.int32))
    tv = jnp.asarray(rng.standard_normal((NQ, 64)).astype(np.float32))
    ti = jnp.asarray(rng.integers(500, 1000, (NQ, 64)).astype(np.int32))
    pv, pi = bs.fold_topk(bv, bi, tv, ti, K, sorted=True)
    mv, mi, (mp,) = bs.fold_topk_payload(bv, bi, (bi * 2,), tv, ti,
                                         (ti * 2,), K)
    mv, mpos = bs.ranked_finish(mv, mi, K)
    # ranked sets agree (payload fold keeps an unsorted carry)
    np.testing.assert_array_equal(np.sort(np.asarray(pv), axis=1),
                                  np.sort(np.asarray(mv), axis=1))
    # payloads rode the same winners: payload ≡ 2 · id by construction
    np.testing.assert_array_equal(np.asarray(mp), 2 * np.asarray(mi))


def test_masked_block_is_noop_on_carry():
    bv, bi = bs.topk_carry(NQ, K)
    rng = np.random.default_rng(5)
    vals = jnp.asarray(rng.standard_normal((NQ, 32)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 99, (NQ, 32)).astype(np.int32))
    # fold one real block, then a fully-masked one: carry must not change
    bv, bi = bs.fold_topk(bv, bi, vals, ids, K, sorted=False)
    v2, i2 = bs.fold_topk(bv, bi, jnp.full_like(vals, jnp.inf),
                          jnp.full_like(ids, -1), K, sorted=False)
    rv, ri = bs.ranked_finish(bv, bi, K)
    r2v, r2i = bs.ranked_finish(v2, i2, K)
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(r2v))
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(r2i))


def test_filter_mask_composes(corpus):
    """+inf'ing a keep-mask's rejects inside score must drop exactly those
    ids from the result — the compose every engine's prefilter uses."""
    data, q = corpus
    n = data.shape[0]
    keep = np.ones(n, bool)
    _, ri = _reference_topk(data, q, K)
    banned = set(map(int, ri[:, 0]))  # ban every query's top hit
    keep[list(banned)] = False
    keepj = jnp.asarray(keep)
    norms = jnp.sum(data.astype(jnp.float32) ** 2, axis=1)
    c = n // 4
    lane = jnp.arange(c, dtype=jnp.int32)

    def score(blk):
        vid = jnp.broadcast_to(blk * c + lane, (NQ, c))
        dots = bs.slab_dots(data[vid][:, None], q)
        dist = norms[vid] - 2.0 * dots.reshape(NQ, c)
        return jnp.where(keepj[vid], dist, jnp.inf), vid

    gv, gi = bs.scan_topk(score, jnp.arange(4, dtype=jnp.int32), NQ, K)
    assert not (set(map(int, np.asarray(gi).ravel())) & banned)
    assert np.isfinite(np.asarray(gv)).all()


def test_topk_carry_id_fill():
    _, bi = bs.topk_carry(3, 4)
    assert (np.asarray(bi) == -1).all()
    _, bi0 = bs.topk_carry(3, 4, id_fill=0)
    assert (np.asarray(bi0) == 0).all()


# ---------------------------------------------------------------------------
# dispatch gate: the platform alone decides


def _dispatch_count(kernel, mode):
    from raft_tpu.obs.metrics import registry

    return registry().counter("raft_pallas_dispatch_total").value(
        kernel=kernel, mode=mode)


@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False)])
def test_gate_resolves_from_platform(monkeypatch, backend, interpret):
    """TPU → Mosaic, anything else → interpret; no artifact, probe or
    env var takes part, and each resolution is counted by mode."""
    monkeypatch.setattr(gate_mod.jax, "default_backend", lambda: backend)
    mode = "interpret" if interpret else "mosaic"
    before = _dispatch_count("select_k", mode)
    assert gate_mod.on_tpu() is (backend == "tpu")
    assert gate_mod.interpret("select_k") is interpret
    assert _dispatch_count("select_k", mode) == before + 1


# ---------------------------------------------------------------------------
# steady state


def test_scan_steady_state(corpus):
    data, q = corpus
    qd = jax.device_put(q)

    @jax.jit
    def run(qx):
        return _scan_over_blocks(data, qx, 4, K)

    jax.block_until_ready(run(qd))  # warm
    with TraceGuard() as tg, jax.transfer_guard("disallow"):
        for _ in range(4):
            jax.block_until_ready(run(qd))
    tg.assert_steady_state()
