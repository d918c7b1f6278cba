"""chip_smoke.py's phases at tiny sizes on the CPU, and the compile-cache
helper every entry point calls.  The Pallas phase runs the kernel through
its interpreter (the ``interpreted_knn_kernel`` fixture): the CPU cannot
compile it."""
import importlib.util
import pathlib
import shutil

import jax
import pytest

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]
TINY = ["--offline", "16", "--offline-updates", "2"]


def _load(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load(ROOT / "chip_smoke.py")


def test_train_resume_and_serve_phases(smoke, tmp_path):
    ck = tmp_path / "fleet_ck"
    a = smoke.phase_train(ck, app="cq_small", fleet=2, epochs=6, every=3,
                          extra=TINY)
    assert a["snapshots"] == [3, 6]
    b = smoke.phase_resume_serve(ck, app="cq_small", fleet=2, start=6,
                                 epochs=9, every=3, serve=6, extra=TINY)
    assert sum(v["n"] for v in b["serve"].values()) == 6
    assert b["round_robin_ms"] > 0


def test_resume_phase_fails_without_a_snapshot_to_resume(smoke, tmp_path):
    with pytest.raises(smoke.SmokeError, match="resumed at epoch"):
        smoke.phase_resume_serve(tmp_path / "empty", app="cq_small",
                                 fleet=2, start=6, epochs=3, every=3,
                                 serve=3, extra=TINY)


def test_pallas_phase_selects_like_top_k(smoke, interpreted_knn_kernel):
    c = smoke.phase_pallas(app="cq_small", fleet=2,
                           require_custom_call=False)
    assert c["actions_equal"] and c["lanes"] == 2


def test_structural_phase(smoke):
    d = smoke.phase_structural(fleet=3, epochs=4)
    assert d["round_robin_ms"] > 0


def test_four_chip_phase_compares_lane_subset(smoke):
    info = smoke.phase_four_chip(app="cq_small", fleet=4, compare=2,
                                 epochs=3)
    assert info["compared_lanes"] == 2
    assert info["max_rel_diff_from_keys"] <= 1e-5
    assert info["max_rel_diff_from_states"] <= 1e-5


def test_main_exits_nonzero_without_a_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_main_exits_nonzero_outside_the_repo(tmp_path, capsys):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    assert _load(tmp_path / "chip_smoke.py").main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_dir_follows_the_variable(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv(compile_cache.ENV_VAR)
    assert compile_cache.compile_cache_dir() == str(ROOT / ".jax_cache")


def test_enable_compile_cache_sets_only_the_fixed_dir(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    metadata = ("jax_compilation_cache_include_metadata_in_key", True)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == [metadata]         # jax reads the variable itself
    monkeypatch.delenv(compile_cache.ENV_VAR)
    calls.clear()
    assert compile_cache.enable_compile_cache() == str(ROOT / ".jax_cache")
    assert calls == [metadata, ("jax_compilation_cache_dir",
                                str(ROOT / ".jax_cache"))]
