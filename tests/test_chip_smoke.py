"""chip_smoke.py's phases on the CPU at a tiny size.

The script itself refuses any platform but a TPU; here the platform check
is lifted by calling the phase functions directly (Pallas kernels run in
interpret mode), so the train → serve → solve flow and its checks are
exercised on every tier-1 run.
"""
import importlib.util
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from repro.sparse.dataset import banded, grid2d, grid3d, scalefree

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def engine(smoke, tmp_path_factory):
    return smoke.train_phase(str(tmp_path_factory.mktemp("smoke")))


@pytest.fixture(scope="module")
def tiny_mats():
    rng = np.random.default_rng(11)
    return [grid2d(9, 9, "g2"), grid3d(4, 4, 4, "g3"),
            banded(60, 4, 0.5, rng, "band"), scalefree(50, 2, rng, "sf")]


def test_device_phase_refuses_cpu(smoke):
    with pytest.raises(smoke.SmokeFailure, match="'cpu'"):
        smoke.device_phase()


def test_main_exits_nonzero_without_result_on_cpu(smoke, capsys):
    assert smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert "platform 'cpu'" in err
    assert '"ok"' not in out


def test_script_alone_exits_nonzero(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_train_phase_builds_main_path_engine(engine):
    cfg = engine.config
    assert (cfg.path, cfg.use_pallas, cfg.backend, cfg.sweep,
            cfg.solve_dtype) == ("device", True, "pipelined", "device",
                                 "fp32_refine")
    assert engine.is_trained


def test_serve_phase_hits_and_matches_host(smoke, engine, tiny_mats):
    names = smoke.serve_phase(engine, tiny_mats, repeats=2)
    host, _ = engine.selector.select_batch(tiny_mats, path="host")
    assert names == list(host)


def test_solve_phase_converges_on_device_path(smoke, engine, tiny_mats):
    recs = smoke.solve_phase(engine, [("g2", tiny_mats[0]),
                                      ("g3", tiny_mats[1])], rhs=(1, 3))
    assert [(r["matrix"], r["k"], r["run"]) for r in recs] == [
        ("g2", 1, "cold"), ("g2", 1, "warm"), ("g2", 3, "cold"),
        ("g3", 1, "cold"), ("g3", 1, "warm"), ("g3", 3, "cold")]
    for r in recs:
        assert r["converged"] and r["residual"] <= smoke.RESIDUAL_TOL
        assert r["residual_path"] == "device"


def test_solve_phase_rejects_unconverged(smoke, engine, tiny_mats,
                                         monkeypatch):
    import repro.sparse.refine as refine

    # a loop that gives up above its tolerance must fail the phase
    monkeypatch.setattr(refine, "_should_stop", lambda *a: (True, False))
    with pytest.raises(smoke.SmokeFailure, match="did not converge"):
        smoke.solve_phase(engine, [("g2", tiny_mats[0])], rhs=(1,))


def test_mesh_phase_degenerate_mesh(smoke, engine, tiny_mats):
    names = smoke.mesh_phase(engine, tiny_mats, n_devices=1)
    assert len(names) == len(tiny_mats)


def test_compile_cache_placement(tmp_path, monkeypatch):
    """The environment's directory wins and nothing is set in code;
    without it the cache goes to <root>/.jax_cache."""
    import jax

    from repro.launch.compile_cache import configure_compile_cache

    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert configure_compile_cache(str(tmp_path)) == str(tmp_path / "env")
        assert (jax.config.jax_compilation_cache_dir,
                jax.config.jax_persistent_cache_min_compile_time_secs) == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = configure_compile_cache(str(tmp_path))
        assert got == str(tmp_path / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev[1])
