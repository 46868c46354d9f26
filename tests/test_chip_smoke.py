"""``chip_smoke.py`` rehearsed on the CPU at a tiny fact-table scale.

The script's phases and checks are the ones the chip runs; here the fact
tables are cut to a few thousand rows (the dimension tables keep their SSB
SF1 sizes) so the run stays quick.  Its main entry must refuse a host
without a TPU.
"""
import importlib.util
import json
from pathlib import Path

import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("pallas", ["0", "1"])
def test_phases_pass_on_cpu(chip_smoke, monkeypatch, pallas):
    """Phases A-C hold every check; with the kernels forced (interpret
    mode) the star join's parts join traces the radix probe, as on the
    chip."""
    monkeypatch.setenv("REPRO_PALLAS", pallas)
    recs = chip_smoke.run(chips=1, scale=0.002, seed=1)
    assert [r["phase"] for r in recs] == ["A", "B", "C"]
    assert recs[0]["fused_fragments"] >= 2
    assert ("radix_hash_probe" in recs[0]["kernels"]) == (pallas == "1")


def test_sharded_phase_runs_on_four_devices(chip_smoke, eight_device_mesh):
    (rec,) = chip_smoke.run(chips=4, scale=0.004, seed=2)
    assert rec["sharded"]["devices"] == 4
    assert rec["one_chip"]["devices"] == 1


def test_refuses_a_host_without_tpu(chip_smoke, capsys, monkeypatch,
                                   tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    lines = out.out.strip().splitlines()
    assert json.loads(lines[-1])["device"]["platform"] == "cpu"
    assert '"ok"' not in out.out
    assert "not a TPU" in out.err


def test_compile_cache_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it
    the cache sits at the checkout's fixed .jax_cache."""
    import jax

    from repro.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = enable_compile_cache()
        assert path == str(_PATH.parent / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
