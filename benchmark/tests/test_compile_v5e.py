"""Each cell's bucket programs, compiled at full size for a described TPU
v5e chip, with no chip: what the chip's compiler refuses, or a program
that does not fit one chip's 16 GB, fails here first.

The topology is described inside a fixture, never at import: only one
process may load the TPU library.  The program resolves its kernels and
tuned tables from the platform it runs on; the test steers that to the
chip's resolution (Mosaic, no tuned table) for its own duration.
"""

import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import harness, spec
from benchmark.tests import tiny

CHIP_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_chip(monkeypatch):
    """Resolve kernels and tables as on the chip; the persistent cache is
    kept out (a compile for a described chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from raft_tpu.neighbors import _packing
    from raft_tpu.ops import blocked_scan
    from raft_tpu.ops.pallas import gate

    monkeypatch.setattr(gate, "on_tpu", lambda: True)
    monkeypatch.setattr(_packing, "_probe_block_table", lambda: {})
    monkeypatch.setattr(_packing, "_probe_block_cache", {})
    monkeypatch.setattr(blocked_scan, "_scan_kernel_table", lambda: {})
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, s):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=s)


def _searcher(cell, s):
    cfg = cell.config
    n, d, k = (int(cfg["data"][key]) for key in ("rows", "dim", "k"))
    dtype = jnp.dtype(cfg["data"]["dtype"])
    if cfg["family"] == "ivf_flat":
        from raft_tpu.neighbors import ivf_flat

        lists = int(cfg["index"]["n_lists"])
        cap = math.ceil(float(cfg["index"]["list_cap_ratio"]) * n / lists)
        index = ivf_flat.IvfFlatIndex(
            _spec((lists, d), dtype, s), _spec((lists, cap, d), dtype, s),
            _spec((lists, cap), jnp.int32, s), _spec((lists,), jnp.int32, s),
            _spec((lists, cap), jnp.float32, s), cfg["data"]["metric"])
        return ivf_flat.searcher(index, k, ivf_flat.IvfFlatSearchParams(
            **cfg["search"]))
    from raft_tpu.neighbors.brute_force import _fast_knn_impl
    from raft_tpu.serve.searchers import BruteForceSearchParams

    p = BruteForceSearchParams(**cfg["search"])
    assert p.mode == "fast"

    def fn(q, y):
        return _fast_knn_impl(q, y, k, p.metric, max(p.cand, k), 1024, 1024,
                              None, p.cut, p.refine_precision)
    return fn, (_spec((n, d), dtype, s),)


@pytest.mark.parametrize("workload", tiny.WORKLOADS)
def test_cell_buckets_compile_for_one_v5e(one_chip, as_on_chip, workload):
    cell = spec.load_cell(workload)
    fn, operands = _searcher(cell, one_chip)
    dtype = jnp.dtype(cell.config["data"]["dtype"])
    ladder = cell.config["server"]["ladder"]
    for bucket in harness.warm_buckets(ladder, cell.traffic):
        q = _spec((bucket, int(cell.config["data"]["dim"])), dtype, one_chip)
        compiled = jax.jit(fn).lower(q, *operands).compile()
        mem = compiled.memory_analysis()
        if mem is not None:
            total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes)
            assert total < CHIP_BYTES, (bucket, total)
        if cell.config["family"] == "brute_force":
            assert "tpu_custom_call" in compiled.as_text()
