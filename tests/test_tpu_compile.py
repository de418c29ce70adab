"""Compile the main path's kernels for a described TPU v5e, with no chip.

The rest of the suite runs Pallas in interpret mode, which never meets
the chip's compiler.  Here each kernel is lowered with
``interpret=False`` at the widths the library runs it at, for a
``v5e:2x2`` topology that the installed TPU compiler describes without
hardware, and must come out as a Mosaic ``tpu_custom_call``.  The exact
kNN program and the fleet's sharded IVF-Flat program are compiled at
deployment size too, for one chip and for the four-chip mesh.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and pytest-xdist workers all
import this file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

# (name, builder) — each builder takes a one-chip sharding and returns
# (jitted fn, args) whose lowering must hold a Mosaic kernel
_KERNELS = {
    # matrix.select_k's partial-bitonic arm at its default blocks
    "select_k_1024x2048_k64": ("select_k", (1024, 2048), 64, 256, 2048),
    "select_k_256x16384_k32": ("select_k", (256, 16384), 32, 256, 2048),
    # brute_force.knn(mode="fast") runs bm=bn=1024 over SIFT-1M's rows
    "fused_l2_topk_bf16_1024x1Mx128": ("fused_l2", jnp.bfloat16,
                                       1024, 1_000_000, 1024, 1024),
    "fused_l2_topk_bf16_256x8192x128": ("fused_l2", jnp.bfloat16,
                                        256, 8192, 256, 2048),
    "fused_l2_topk_int8_256x8192x128": ("fused_l2", jnp.int8,
                                        256, 8192, 256, 2048),
    # blocked_scan.scan_topk_fused's slab kernel at its default blocks
    "fused_slab_topk_256x4096x128": ("fused_slab", 256, 4096, 8, 512),
}

# the device op each kind of kernel shows up as
_OP_NAMES = {"select_k": "select_k", "fused_l2": "fused_l2_topk",
             "fused_slab": "fused_scan"}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_program(case, s):
    kind = case[0]
    if kind == "select_k":
        from raft_tpu.ops.pallas import select_k

        _, shape, k, bm, bn = case
        return select_k.select_k, (_spec(shape, jnp.float32, s), k, bm, bn,
                                False)
    if kind == "fused_l2":
        from raft_tpu.ops.pallas import fused_l2_topk

        _, dtype, m, n, bm, bn = case
        return fused_l2_topk.fused_l2_topk, (
            _spec((m, 128), dtype, s), _spec((n, 128), dtype, s),
            _spec((1, n), jnp.float32, s), bm, bn, False)
    from raft_tpu.ops.pallas import fused_scan

    _, nq, c, bm, bn = case
    return fused_scan.fused_scan, (
        _spec((nq, 128), jnp.bfloat16, s),
        _spec((nq, c, 128), jnp.bfloat16, s),
        _spec((nq, c), jnp.float32, s), bm, bn, False)


@pytest.mark.parametrize("name", sorted(_KERNELS))
def test_pallas_kernel_compiles_for_v5e(one_chip, name):
    case = _KERNELS[name]
    fn, args = _kernel_program(case, one_chip)
    text = fn.lower(*args).compile().as_text()
    # the instruction's name is what the profiler's "XLA Ops" line shows
    kernel = [line.lstrip() for line in text.splitlines()
              if "tpu_custom_call" in line]
    assert kernel and all(line.startswith(f"%{_OP_NAMES[case[0]]}.")
                          for line in kernel), kernel


def test_exact_knn_compiles_for_one_v5e(one_chip):
    """The exact ground-truth program chip_smoke.py runs: 1,000 queries
    against SIFT-1M's 1M×128 f32 rows, k=10, knn()'s default tile."""
    from raft_tpu.neighbors.brute_force import _knn_impl

    q = _spec((1000, 128), jnp.float32, one_chip)
    db = _spec((1_000_000, 128), jnp.float32, one_chip)
    compiled = _knn_impl.lower(q, db, 10, "sqeuclidean", 8192).compile()
    mem = compiled.memory_analysis()
    assert mem is None or mem.argument_size_in_bytes >= 512_000_000


def test_ivf_pq_encode_fits_one_v5e(one_chip):
    """IVF-PQ build encodes SIFT-1M's 1M residuals (pq_dim 32, 8 bits)
    inside one chip's 16 GB: unchunked it needed 15.85 GB and the chip
    refused it (chip_smoke.py, PR 21)."""
    from raft_tpu.neighbors.ivf_pq import _encode

    compiled = _encode.lower(_spec((1_000_000, 128), jnp.float32, one_chip),
                             _spec((32, 256, 4), jnp.float32, one_chip),
                             m=32).compile()
    mem = compiled.memory_analysis()
    if mem is not None:
        assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes) < 8e9


def test_fleet_ivf_flat_compiles_for_v5e_2x2(topo):
    """The fleet's sharded IVF-Flat fan-out over the four described chips
    at chip_smoke.py --multichip's DEEP-10M shapes: 1024 lists of d=96,
    10M rows at list_cap_ratio 1.25, 64 queries — one all-gather merge,
    inside one chip's 16 GB per device."""
    from raft_tpu.serve.fleet import _ivf_flat_fleet_program

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("shard",))
    rep, sh = NamedSharding(mesh, P()), NamedSharding(mesh, P("shard"))
    lists, cap, d, nq = 1024, 12_208, 96, 64
    fn = _ivf_flat_fleet_program(mesh, "shard", 10, 32, "sqeuclidean", 1,
                                 lists // 4, False, False, lists)
    args = (_spec((nq, d), jnp.float32, rep),
            _spec((lists, d), jnp.float32, rep),
            _spec((lists, cap, d), jnp.float32, sh),
            _spec((lists, cap), jnp.int32, sh),
            _spec((lists,), jnp.int32, sh),
            _spec((lists, cap), jnp.float32, sh))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "all-gather" in compiled.as_text()
    mem = compiled.memory_analysis()
    if mem is not None:
        per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                      + mem.temp_size_in_bytes)
        assert per_device < 16e9


def test_fleet_ivf_flat_grouped_compiles_for_v5e_2x2(topo, monkeypatch):
    """The fleet's IVF-Flat fan-out on the grouped scan over the four
    described chips at the SIFT-1M-class cell's widths: 1024 lists of
    1960 × 128 f32 rows, 512 queries × 32 probes — each shard lowers
    ``ivf_grouped_scan`` through Mosaic, then one all-gather merge."""
    from raft_tpu.ops.pallas import gate
    from raft_tpu.serve.fleet import _ivf_flat_fleet_program

    monkeypatch.setattr(gate, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("shard",))
    rep, sh = NamedSharding(mesh, P()), NamedSharding(mesh, P("shard"))
    lists, cap, d, nq = 1024, 1960, 128, 512
    fn = _ivf_flat_fleet_program(mesh, "shard", 10, 32, "sqeuclidean", 1,
                                 lists // 4, False, True, lists)
    args = (_spec((nq, d), jnp.float32, rep),
            _spec((lists, d), jnp.float32, rep),
            _spec((lists, cap, d), jnp.float32, sh),
            _spec((lists, cap), jnp.int32, sh),
            _spec((lists,), jnp.int32, sh),
            _spec((lists, cap), jnp.float32, sh))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "all-gather" in text
    assert any("tpu_custom_call" in line and "ivf_grouped_scan" in line
               for line in text.splitlines())


@pytest.mark.parametrize("cap,rows", [(1954, 512), (1960, 512), (1960, 1)])
def test_ivf_flat_grouped_search_compiles_for_one_v5e(one_chip, monkeypatch,
                                                      cap, rows):
    """The served IVF-Flat program at the SIFT-1M-class cell's widths:
    ``rows`` queries × 32 probes over 1024 lists of ``cap`` × 128 f32
    rows, k = 10.  At 512 rows it takes the grouped (list-major) scan,
    lowered through Mosaic as ``ivf_grouped_scan``; at 1 row the
    query-major one.  At 1960, the slab capacity the build stores for
    1954 rows a list (padded to the f32 sublane tile), the slab is stored
    list-major and nothing in either program copies it."""
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.ops.pallas import gate

    monkeypatch.setattr(gate, "on_tpu", lambda: True)
    lists, d = 1024, 128
    index = ivf_flat.IvfFlatIndex(
        _spec((lists, d), jnp.float32, one_chip),
        _spec((lists, cap, d), jnp.float32, one_chip),
        _spec((lists, cap), jnp.int32, one_chip),
        _spec((lists,), jnp.int32, one_chip),
        _spec((lists, cap), jnp.float32, one_chip), "sqeuclidean")
    fn, ops = ivf_flat.searcher(index, 10, ivf_flat.IvfFlatSearchParams(
        n_probes=32))
    text = jax.jit(fn).lower(_spec((rows, d), jnp.float32, one_chip),
                             *ops).compile().as_text()
    kernel = [line.lstrip() for line in text.splitlines()
              if "tpu_custom_call" in line and "ivf_grouped_scan" in line]
    if ivf_flat.grouped_batch(rows, 32, lists):
        assert kernel and kernel[0].startswith("%ivf_grouped_scan."), kernel
    else:
        assert not kernel, kernel
    slab = f"f32[{lists},{cap},{d}]"
    made = [line.split(" = ")[0].strip() for line in text.splitlines()
            if f"= {slab}" in line and "parameter(" not in line
            and "get-tuple-element" not in line]
    if cap % 8 == 0:
        assert not made, made


def _deep10m_ivf_pq():
    """The DEEP-10M-class IVF-PQ deployment of the benchmark's
    ``deep10m-ivf_pq`` configuration."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "deep10m-ivf_pq.json")) as f:
        return json.load(f)


def test_ivf_pq_decode_fits_one_v5e(one_chip):
    """The build's decode step at DEEP-10M-class size: 4096 lists of 3664
    slots, 48 sub-codes a row, into the 3.8 GB bf16 slab (rows padded to
    128 lanes).  Stacking the decoded blocks held 14 GB, more than fits
    beside the 3.84 GB base."""
    from raft_tpu.neighbors import ivf_pq

    cfg = _deep10m_ivf_pq()
    n, d = cfg["data"]["rows"], cfg["data"]["dim"]
    lists, m = cfg["index"]["n_lists"], cfg["index"]["pq_dim"]
    cap = ivf_pq.slab_slots(int(np.ceil(
        ivf_pq.IvfPqIndexParams().list_cap_ratio * n / lists)))
    compiled = ivf_pq._decode_slab.lower(
        _spec((lists, cap, m), jnp.uint8, one_chip),
        _spec((lists, d), jnp.float32, one_chip),
        _spec((m, 256, d // m), jnp.float32, one_chip),
        _spec((lists, cap), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    if mem is not None:
        assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes) + 4 * n * d < 16e9


def _served_ivf_pq(one_chip, monkeypatch, rows):
    """The served ``rows``-row program of the ``deep10m-ivf_pq.batch``
    cell, compiled for one v5e: the recon-tier scan at 40 candidates over
    4096 lists of the build's 3664 slots (3663 rows a list, padded to
    bf16's sublane tile) × 96 (stored as 128 lanes), 64 probes, and the
    exact re-rank over the 10M × 96 f32 base, with the index and the base
    as its operands."""
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.neighbors.refine import Refined
    from raft_tpu.neighbors import _packing
    from raft_tpu.ops import blocked_scan
    from raft_tpu.ops.pallas import gate
    from raft_tpu.serve import make_searcher

    monkeypatch.setattr(gate, "on_tpu", lambda: True)
    monkeypatch.setattr(_packing, "_probe_block_table", lambda: {})
    monkeypatch.setattr(_packing, "_probe_block_cache", {})
    monkeypatch.setattr(blocked_scan, "_scan_kernel_table", lambda: {})
    cfg = _deep10m_ivf_pq()
    n, d, k = (cfg["data"][key] for key in ("rows", "dim", "k"))
    lists, m = cfg["index"]["n_lists"], cfg["index"]["pq_dim"]
    cap = ivf_pq.slab_slots(int(np.ceil(
        ivf_pq.IvfPqIndexParams().list_cap_ratio * n / lists)))
    assert cap == 3664

    def s(shape, dtype):
        return _spec(shape, dtype, one_chip)

    index = ivf_pq.IvfPqIndex(
        s((lists, d), jnp.float32), s((m, 256, d // m), jnp.float32),
        s((lists, cap, m), jnp.uint8), s((lists, cap), jnp.float32),
        s((lists, cap), jnp.int32), s((lists,), jnp.int32), "sqeuclidean",
        recon=s((lists, cap, ivf_pq.recon_lanes(d)), jnp.bfloat16),
        recon_norms=s((lists, cap), jnp.float32),
        centroid_lut=s((lists, m, 256), jnp.float32),
        adc_norms=s((lists, cap), jnp.float32))
    view = Refined(index, s((n, d), jnp.float32), cfg["refine_ratio"])
    fn, ops = make_searcher(view, k, ivf_pq.IvfPqSearchParams(
        **cfg["search"]))
    return jax.jit(fn).lower(s((rows, d), jnp.float32), *ops).compile()


def test_served_ivf_pq_with_refine_fits_one_v5e(one_chip, monkeypatch):
    """The served 512-row program of the ``deep10m-ivf_pq.batch`` cell:
    the recon-tier PQ scan at 40 candidates and their exact re-rank over
    the 10M × 96 f32 base, in one program, under one chip's 16 GB with
    the index and the base as its operands."""
    compiled = _served_ivf_pq(one_chip, monkeypatch, 512)
    mem = compiled.memory_analysis()
    if mem is not None:
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes)
        # every operand is an argument: the base, the slabs, the tables
        assert 7.5e9 < total < 16e9, total


@pytest.mark.parametrize("rows", [512, 1])
def test_ivf_pq_grouped_search_compiles_for_one_v5e(one_chip, monkeypatch,
                                                    rows):
    """The served IVF-PQ program takes the list-major scan of the bf16
    recon slab at 512 rows and, its lists being too large to gather
    whole, at 1 row too: lowered through Mosaic as ``ivf_grouped_scan``.
    The slab, stored at 3664 slots of 128 lanes, is read in place:
    neither program copies or slices the whole of it."""
    text = _served_ivf_pq(one_chip, monkeypatch, rows).as_text()
    kernel = [line.lstrip() for line in text.splitlines()
              if "tpu_custom_call" in line and "ivf_grouped_scan" in line]
    assert kernel and kernel[0].startswith("%ivf_grouped_scan."), kernel
    assert "mini-gather-slice" not in text
    slab = "bf16[4096,3664,128]"
    made = [line.split(" = ")[0].strip() for line in text.splitlines()
            if f"= {slab}" in line and "parameter(" not in line
            and "get-tuple-element" not in line]
    assert not made, made
