"""`chip_smoke.py` refuses to run without a TPU, and its phases' checks
hold on the CPU at small size.

The refusal tests run the script in a child process pinned to the CPU
(`JAX_PLATFORMS=cpu`), so the child never reaches for an accelerator.
"""
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu(tmp_path):
    out = _run(ROOT, ROOT / "chip_smoke.py")
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def test_refuses_outside_the_repo(tmp_path):
    script = tmp_path / "chip_smoke.py"
    shutil.copy(ROOT / "chip_smoke.py", script)
    out = _run(tmp_path, script)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_metrics_diff():
    a = [{"x": 1.0, "v": [1.0, 2.0]}]
    assert chip_smoke.metrics_diff(a, [{"x": 1.0, "v": [1.0, 2.0]}]) == 0.0
    assert chip_smoke.metrics_diff(a, [{"x": 1.5, "v": [1.0, 2.0]}]) == 0.5
    assert chip_smoke.metrics_diff(a, [{"x": 1.0}]) == float("inf")
    with pytest.raises(ValueError):
        chip_smoke.metrics_diff(a, a + a)


def test_reference_phase_on_host(tmp_path):
    """The reference comparison at small size: here both runs are on the
    CPU, so they must agree exactly."""
    cfg = dict(chip_smoke.REFERENCE, seeds=(0,), T=4096)
    r = chip_smoke.phase_reference(tmp_path, cfg)
    assert r["jobs"] == 4 and r["verdicts"] == ["STABLE"]
    assert r["useful_rate_rel_diff_max"] == 0.0
    assert r["mean_queue_rel_diff_max"] == 0.0


def test_check_stream_rejects_a_bad_file(tmp_path):
    bad = tmp_path / "bad_stream.jsonl"
    bad.write_text('{"kind": "fleet"}\n')
    with pytest.raises(chip_smoke.Failed):
        chip_smoke.check_stream(bad)


def test_compile_cache_place(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it the cache
    goes to the one fixed directory in the checkout."""
    import jax

    from repro.runtime import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert updates == []
    monkeypatch.delenv(compile_cache.CACHE_ENV)
    path = str(ROOT / ".jax_cache")
    assert compile_cache.use_compile_cache() == path
    assert updates == [("jax_compilation_cache_dir", path)]
