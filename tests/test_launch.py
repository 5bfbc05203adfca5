"""Exit codes of the entry points: a run that failed must not exit 0."""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.subprocess
@pytest.mark.parametrize("where", ["cpu", "alone"])
def test_chip_smoke_refuses_without_chip(tmp_path, where):
    """``chip_smoke.py`` fails before printing a result on the CPU, and
    when it is run apart from the sources it drives."""
    script = os.path.join(REPO, "chip_smoke.py")
    if where == "alone":
        script = shutil.copy(script, tmp_path)
    res = subprocess.run([sys.executable, script],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0, res.stdout
    lines = res.stdout.strip().splitlines()
    if lines:
        try:
            last = json.loads(lines[-1])
        except json.JSONDecodeError:
            last = None
        assert not (isinstance(last, dict) and last.get("ok")), lines[-1]


def test_serve_exit_code_follows_quarantined_request(monkeypatch):
    """The quarantine wall keeps the serve alive past a failing request;
    ``repro.launch.serve`` still exits non-zero for it."""
    from repro.launch import serve as serve_cli
    from repro.serving.faults import FaultInjector, PrefillError

    monkeypatch.setattr(serve_cli, "enable_compile_cache", lambda: "")
    argv = ["--arch", "granite-3-2b", "--smoke", "--paged", "--scheduler",
            "--num-requests", "2", "--prompt-len", "64", "--max-new", "2",
            "--max-batch", "2"]
    assert serve_cli.main(argv) == 0

    real = serve_cli.ServingEngine.serve
    monkeypatch.setattr(
        serve_cli.ServingEngine, "serve",
        lambda self, reqs, **kw: real(
            self, reqs, faults=FaultInjector(PrefillError(uid=0)), **kw))
    assert serve_cli.main(argv) == 1


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """Where ``JAX_COMPILATION_CACHE_DIR`` is set the launchers leave the
    cache to JAX; otherwise it goes to the fixed ``<repo>/.jax_cache``."""
    import jax
    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            assert got == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
