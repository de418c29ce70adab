"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The device check must refuse the CPU.  Past it, each phase's checks must
hold with the gate patched to take the TPU's paths while the Pallas
kernels run interpreted; only a test may count those as Mosaic.
"""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # its dataclass resolves names there
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def as_mosaic(monkeypatch):
    """The gate as on a TPU, except that kernels still run interpreted."""
    import jax

    from raft_tpu.obs.metrics import registry
    from raft_tpu.ops.pallas import gate

    def interpret(kernel):
        registry().counter("raft_pallas_dispatch_total").inc(
            kernel=kernel, mode="mosaic")
        return True

    monkeypatch.setattr(gate, "on_tpu", lambda: True)
    monkeypatch.setattr(gate, "interpret", interpret)
    jax.clear_caches()  # programs traced under the real gate
    yield
    jax.clear_caches()


def _phases(capsys):
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]


@pytest.mark.parametrize("multichip", [False, True])
def test_device_check_refuses_cpu(smoke, multichip):
    with pytest.raises(AssertionError, match="no TPU"):
        smoke.main(["--multichip"] if multichip else [])


def test_single_chip_phases(smoke, as_mosaic, capsys, monkeypatch):
    monkeypatch.setattr(smoke, "GROUPED_QUERIES", 32)  # interpret is slow
    dep = smoke.Deployment(rows=20_000, dim=128, queries=600, n_lists=32,
                           n_probes=8)
    smoke.run_single(dep, seed=0)
    lines = _phases(capsys)
    assert [ln["phase"] for ln in lines] == [
        "data", "exact", "fast", "ivf_flat", "ivf_pq", "kernels"]
    for ln in lines:
        assert "error" not in ln, ln
    by = {ln["phase"]: ln for ln in lines}
    assert by["ivf_flat"]["grouped_vs_xla_recall"] >= smoke.GROUPED_AGREE
    assert by["ivf_pq"]["recall"] >= smoke.IVF_RECALL


def test_multichip_phases(smoke, as_mosaic, capsys):
    dep = smoke.Deployment(rows=20_000, dim=32, queries=64,
                           rows_per_blob=100, n_lists=32, n_probes=8,
                           list_cap_ratio=1.25)
    smoke.run_multichip(dep, seed=0)
    by = {ln["phase"]: ln for ln in _phases(capsys)}
    assert set(by) == {"data", "build", "single_device", "fleet",
                       "fleet_server"}
    assert len(set(by["fleet"]["shard_devices"])) == 4
    assert by["fleet"]["ids_match_untied"]
