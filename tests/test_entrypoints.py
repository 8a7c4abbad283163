"""Start-up contracts of the entry points: the persistent compile cache and
the chip smoke check's refusal to run without a TPU."""
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run(args, cwd, **env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("PYTHONPATH", compile_cache.ENV_VAR)}
    base["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, *args], cwd=cwd, env={**base, **env},
                          capture_output=True, text=True, timeout=300)


class TestCompileCache:
    def test_env_var_places_the_cache(self, tmp_path):
        """With the variable set, compiles land there and the checkout's
        default directory is left alone."""
        cache = tmp_path / "cache"
        before = (sorted(os.listdir(compile_cache.DEFAULT_DIR))
                  if compile_cache.DEFAULT_DIR.exists() else None)
        script = ("import jax, repro.launch.compile_cache as c; "
                  "print(c.use_compile_cache()); "
                  "jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()")
        res = _run(["-c", script], ROOT, PYTHONPATH=str(ROOT / "src"),
                   JAX_COMPILATION_CACHE_DIR=str(cache),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
        assert res.returncode == 0, res.stderr
        assert res.stdout.strip() == str(cache)
        assert any(cache.iterdir())
        after = (sorted(os.listdir(compile_cache.DEFAULT_DIR))
                 if compile_cache.DEFAULT_DIR.exists() else None)
        assert after == before

    def test_default_is_a_fixed_ignored_path_in_the_checkout(
            self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        was_dir = jax.config.jax_compilation_cache_dir
        was_on = jax.config.jax_enable_compilation_cache
        try:
            path = compile_cache.use_compile_cache()
            assert path == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", was_dir)
            jax.config.update("jax_enable_compilation_cache", was_on)
        ignored = (ROOT / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored


class TestChipSmokeRefusesCpu:
    def test_no_tpu_exits_nonzero_without_ok(self):
        res = _run(["chip_smoke.py"], ROOT)
        assert res.returncode != 0
        assert '"ok": true' not in res.stdout
        assert "needs a TPU" in res.stderr

    def test_alone_without_the_repo_fails(self, tmp_path):
        shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
        res = _run(["chip_smoke.py"], tmp_path)
        assert res.returncode != 0
        assert '"ok": true' not in res.stdout


@pytest.mark.parametrize("argv,reduced", [
    ([], True), (["--reduced"], True), (["--no-reduced"], False)])
def test_serve_launcher_reaches_published_widths(monkeypatch, argv, reduced):
    """``--reduced`` is the CPU default; ``--no-reduced`` serves the
    published widths."""
    from repro.launch import serve

    seen = {}

    def stop(name, reduced=False):
        seen["reduced"] = reduced
        raise SystemExit(0)

    monkeypatch.setattr(serve, "get_config", stop)
    monkeypatch.setattr(serve, "use_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", ["serve", *argv])
    with pytest.raises(SystemExit):
        serve.main()
    assert seen["reduced"] is reduced
