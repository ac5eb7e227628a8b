"""chip_smoke.py at a tiny size on the CPU: the same phases and the same
comparisons the chip run makes, plus the two refusals — no ``"ok": true``
without a TPU, and the compile cache goes where the environment says."""

import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def test_storm_phase_agrees_with_oracle():
    # B > G: most groups get several lanes per step, so slot ranking
    # inside a batch and the window wrap (4 steps x ~4 lanes > W=8) are
    # both held to the oracle
    out = chip_smoke.storm_phase(G=64, W=8, B=256, steps=4, sample=48,
                                 seed=3)
    assert out["decided"] == out["lanes_admitted"] > 0
    assert out["oracle_sample_groups"] == 48
    assert out["oracle_sample_decided"] > 48
    assert out["oracle_values_compared"] > 48 * 3 * 2


def test_served_phase_agrees_with_scalar_replay(capsys):
    # the chip fuses whole waves (PC.FUSE_WAVES "auto" on an
    # accelerator): rehearse the handlers the chip run will take
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.utils.config import Config
    Config.set(PC.FUSE_WAVES, "on")
    out = chip_smoke.served_phase(
        n_groups=32, capacity=1024, window=16, n_active=24, rounds=10,
        concurrency=16, seed=5, platform="cpu")
    assert out["requests"] == 240 and out["scalar_replay_agrees"]
    # conftest's eight virtual devices: the default path is the mesh
    assert out["mesh"] == len(jax.local_devices())
    assert '"ok": true' not in capsys.readouterr().out


def test_mesh_phase_agrees_with_mesh_off():
    n_dev = len(jax.local_devices())
    if n_dev < 2:
        pytest.skip("needs the virtual device mesh")
    out = chip_smoke.served_phase(
        n_groups=8 * n_dev - 4, capacity=8 * n_dev, window=16,
        n_active=16, rounds=3, concurrency=8, seed=7, platform="cpu",
        mesh_ab=True)
    assert out["mesh"] == n_dev and out["mesh_off_agrees"]


def test_main_refuses_without_a_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--mesh"]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    from gigapaxos_tpu.utils import jaxcache

    prior = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jaxcache, "_enabled", None)
    try:
        # JAX reads JAX_COMPILATION_CACHE_DIR into the config at import;
        # stand in for that, then see the program set no other
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jaxcache.enable_persistent_cache()
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        assert jaxcache.cache_metrics()["dir"] == str(tmp_path)

        monkeypatch.setattr(jaxcache, "_enabled", None)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        jaxcache.enable_persistent_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert jaxcache.cache_metrics()["dir"] == \
            os.path.join(repo, ".jax_cache") == \
            jax.config.jax_compilation_cache_dir
    finally:
        # monkeypatch puts jaxcache._enabled and the environment back
        jax.config.update("jax_compilation_cache_dir", prior)


def test_multiproc_columnar_is_refused_off_the_cpu(monkeypatch):
    """One process per chip: N columnar server children cannot share
    it, so the combination is refused before anything boots — unless
    the environment keeps every child on host XLA."""
    import argparse

    from gigapaxos_tpu.testing.main import throughput_multiproc

    monkeypatch.delenv("JAX_PLATFORMS")
    args = argparse.Namespace(backend="columnar", nodes=3)
    with pytest.raises(SystemExit, match="a chip belongs to one process"):
        throughput_multiproc(args)
