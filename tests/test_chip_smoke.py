"""chip_smoke.py and the compile-cache contract it prints.

The smoke's real assertions (platform, HBM) only mean something on
the chip; what tier-1 can pin on CPU is that the script still runs end to
end through the public entry points at a tiny size, in 32-bit mode like the
chip, that it REFUSES a CPU at full-size arguments, and that the compile
cache lives where the contract says.  Each case is a child process: the
platform, x64 and cache directory all latch at first jax use.
"""
import json
import os
import shutil
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ROOT
    env["TMOG_COST_HISTORY"] = ""
    # the chip path is 32-bit; conftest's x64 must not leak into the child
    env.pop("JAX_ENABLE_X64", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def _smoke_copy(tmp_path):
    """chip_smoke.py alone in a scratch directory (its outputs land beside
    it); the package comes from PYTHONPATH."""
    return shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)


class TestChipSmoke:
    def test_runs_tiny_on_cpu(self, tmp_path):
        script = _smoke_copy(tmp_path)
        cache = str(tmp_path / "jax_cache")
        out = subprocess.run(
            [sys.executable, script, "--rows", "3000", "--cols", "32"],
            capture_output=True, text=True, timeout=900, cwd=str(tmp_path),
            env=_env(JAX_COMPILATION_CACHE_DIR=cache))
        assert out.returncode == 0, out.stderr[-3000:]
        lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
        last = json.loads(lines[-1])
        assert last["ok"] is True
        assert last["device"]["platform"] == "cpu"
        assert set(last["device"]) == {"platform", "kind", "count"}
        text = out.stdout
        assert "x64=false" in text
        assert f'[smoke:cache] dir="{cache}" from_env=true' in text
        assert "[smoke:serve.aot] buckets=7 aot_loads=7 aot_misses=0" in text
        for leg in ("sweep.cold", "sweep.warm", "gbt", "serve.lr",
                    "serve.winner", "done"):
            assert f"[smoke:{leg}]" in text, leg

    def test_refuses_a_cpu_at_full_size(self, tmp_path):
        script = _smoke_copy(tmp_path)
        out = subprocess.run(
            [sys.executable, script], capture_output=True, text=True,
            timeout=300, cwd=str(tmp_path), env=_env())
        assert out.returncode != 0
        assert "no accelerator" in out.stderr
        assert '"ok"' not in out.stdout

    def test_fails_alone_in_a_directory(self, tmp_path):
        script = _smoke_copy(tmp_path)
        env = _env()
        del env["PYTHONPATH"]
        out = subprocess.run(
            [sys.executable, script], capture_output=True, text=True,
            timeout=300, cwd=str(tmp_path), env=env)
        assert out.returncode != 0
        assert '"ok"' not in out.stdout


_PRINT_CACHE_DIR = (
    "import jax\n"
    "from transmogrifai_tpu.utils.compile_cache import "
    "enable_persistent_cache\n"
    "d = enable_persistent_cache()\n"
    "assert d == jax.config.jax_compilation_cache_dir\n"
    "assert enable_persistent_cache() == d\n"
    "print('CACHE_DIR=' + d)\n")


class TestCompileCacheContract:
    def _dir(self, env):
        out = subprocess.run([sys.executable, "-c", _PRINT_CACHE_DIR],
                             capture_output=True, text=True, timeout=240,
                             env=env)
        return out, [ln[len("CACHE_DIR="):] for ln in out.stdout.splitlines()
                     if ln.startswith("CACHE_DIR=")]

    def test_environment_directory_is_left_alone(self, tmp_path):
        want = str(tmp_path / "from_env")
        out, got = self._dir(_env(JAX_COMPILATION_CACHE_DIR=want))
        assert out.returncode == 0, out.stderr[-2000:]
        assert got == [want]
        assert os.path.isdir(want)

    def test_default_is_the_fixed_checkout_directory(self):
        out, got = self._dir(_env())
        assert out.returncode == 0, out.stderr[-2000:]
        assert got == [os.path.join(ROOT, ".jax_cache")]
        assert not got[0].startswith(tempfile.gettempdir())

    def test_no_cache_path_from_tempfile_pid_or_time(self):
        import inspect

        from transmogrifai_tpu.utils import compile_cache

        src = inspect.getsource(compile_cache)
        for word in ("tempfile", "getpid", "time.", "mkdtemp"):
            assert word not in src, word

    def test_a_cache_that_cannot_be_enabled_raises(self, tmp_path):
        blocker = tmp_path / "a_file"
        blocker.write_text("not a directory")
        out, got = self._dir(_env(
            JAX_COMPILATION_CACHE_DIR=str(blocker / "cache")))
        assert out.returncode != 0 and got == []
