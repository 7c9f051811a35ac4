"""`chip_smoke.py` off the chip: its three phases hold the jax backend to
the numpy oracle at a small m, a diverging plan makes them fail, and
its entry point refuses to run anywhere but on a TPU.  Also the compile
cache helper the entry points share (`benchmarks/compile_cache.py`).
"""
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    import chip_smoke
    return chip_smoke


@pytest.fixture
def cache_dir_restored():
    import jax
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_phases_agree_numpy_vs_jax(smoke):
    plan, hw, facts = smoke.phase_provision(30)
    assert facts["n_devices"] == plan.n_gpus > 0
    assert facts["hardware"] == hw.name
    sim = smoke.phase_simulate(30, plan, hw)
    assert sim["requests"] > 0
    assert sim["max_rel_latency_diff"] <= smoke.TOL["rtol"]
    ctl = smoke.phase_control(30, plan, hw)
    assert ctl["n_reconfigs"] > 0 and ctl["final_placements"] > 0


def test_provision_phase_catches_a_diverging_plan(smoke, monkeypatch):
    """One extra r_unit on every newcomer grant of the jax twin must
    change the plan, and the phase must refuse it."""
    from repro.core import perf_model_jax as pmj
    real = pmj.alloc_all_jax

    def drifted(cl, spec, coeffs, batch, r_lower):
        feasible, rr, rn, r_inter = real(cl, spec, coeffs, batch, r_lower)
        return feasible, rr, rn + cl.hw.r_unit, r_inter

    monkeypatch.setattr(pmj, "alloc_all_jax", drifted)
    with pytest.raises(AssertionError, match="placements differ"):
        smoke.phase_provision(10)


def test_main_refuses_a_cpu(smoke, capsys, monkeypatch, tmp_path,
                            cache_dir_restored):
    import jax
    assert jax.devices()[0].platform == "cpu"     # JAX_PLATFORMS=cpu
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert smoke.main() != 0
    out, err = capsys.readouterr()
    assert '"ok": true' not in out
    assert "'cpu'" in err


def test_cache_helper_leaves_a_set_dir_alone(monkeypatch, tmp_path,
                                             cache_dir_restored):
    import jax
    from benchmarks.compile_cache import setup_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == cache_dir_restored


def test_cache_helper_default_is_one_repo_path(monkeypatch,
                                               cache_dir_restored):
    import jax
    from benchmarks.compile_cache import setup_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = setup_compile_cache(), setup_compile_cache()
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
