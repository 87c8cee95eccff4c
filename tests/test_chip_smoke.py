"""The entry scripts: chip_smoke.py's checks, its refusal to run without a
TPU, the shared compile-cache helper, and the benchmark runner's exit code.

chip_smoke's searches run here at a toy size with the kernels in Pallas
interpret mode; on the chip the same code runs them compiled at n=11.
"""
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "examples")]
import chip_smoke  # noqa: E402
from repro.launch import compile_cache  # noqa: E402


def test_smoke_checks_pass_in_interpret_mode(capsys):
    assert chip_smoke.run_checks(6, 5, "interpret") == []
    out = capsys.readouterr().out
    assert "pancake n=6 impl=interpret level counts: " \
           "[1, 5, 20, 79, 199, 281, 133, 2]" in out
    assert "check (c) n=5 interpret == tier D: ok" in out


def test_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.enable_compile_cache() == os.path.join(REPO,
                                                                ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(REPO,
                                                                ".jax_cache")


def test_compile_cache_env_dir_wins(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_bench_run_exits_nonzero_on_failed_section(monkeypatch, capsys,
                                                   tmp_path):
    from benchmarks import constructs, run

    def boom():
        raise RuntimeError("section broke")

    monkeypatch.setattr(constructs, "bench_constructs", boom)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    out = tmp_path / "b.json"
    monkeypatch.setattr(sys, "argv", ["run", "--only", "constructs",
                                      "--json", str(out)])
    assert run.main() == 1
    assert "constructs_FAILED" in capsys.readouterr().out
    assert json.loads(out.read_text())["errors"] == {
        "constructs": "RuntimeError('section broke')"}
