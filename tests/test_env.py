"""launch.env: XLA flag composition, tcmalloc discovery, and the
argparse glue shared by the serve/train launchers.  Everything here
must degrade to a no-op on machines without the optional pieces."""

import argparse
import os

import pytest

from repro.launch import env as envmod


class TestXlaFlags:
    def test_host_device_count(self, monkeypatch):
        monkeypatch.setattr(os, "cpu_count", lambda: 8)
        out = envmod.xla_flags(host_device_count=2, existing="")
        assert out == "--xla_force_host_platform_device_count=2"

    def test_existing_flags_win(self):
        # a user-exported value of the same flag is never clobbered
        out = envmod.xla_flags(
            host_device_count=8,
            existing="--xla_force_host_platform_device_count=4")
        assert out == "--xla_force_host_platform_device_count=4"

    def test_gpu_preset_appends_without_duplicates(self):
        out = envmod.xla_flags(
            platform="gpu",
            existing="--xla_gpu_triton_gemm_any=false")
        flags = out.split()
        assert "--xla_gpu_triton_gemm_any=false" in flags
        assert sum(f.startswith("--xla_gpu_triton_gemm_any")
                   for f in flags) == 1
        assert any(f.startswith("--xla_gpu_enable_latency_hiding")
                   for f in flags)

    def test_count_capped_at_cores(self, monkeypatch):
        monkeypatch.setattr(os, "cpu_count", lambda: 4)
        with pytest.warns(UserWarning, match="capping"):
            out = envmod.xla_flags(host_device_count=64, existing="")
        assert out == "--xla_force_host_platform_device_count=4"

    def test_reads_environ_by_default(self, monkeypatch):
        monkeypatch.setenv("XLA_FLAGS", "--foo=1")
        assert envmod.xla_flags(host_device_count=2).split()[0] == "--foo=1"


class TestTcmalloc:
    def test_env_pairs_or_empty(self):
        env = envmod.tcmalloc_env()
        lib = envmod.find_tcmalloc()
        if lib is None:
            assert env == {}
        else:
            assert lib in env["LD_PRELOAD"]
            assert "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD" in env

    def test_preload_not_duplicated(self, monkeypatch):
        lib = envmod.find_tcmalloc()
        if lib is None:
            pytest.skip("no libtcmalloc in image")
        monkeypatch.setenv("LD_PRELOAD", lib)
        assert envmod.tcmalloc_env()["LD_PRELOAD"].split(":").count(lib) == 1


class TestApply:
    def test_sets_and_reports_changes(self, monkeypatch):
        monkeypatch.delenv("REPRO_TEST_KEY", raising=False)
        changed = envmod.apply({"REPRO_TEST_KEY": "1"})
        assert changed == {"REPRO_TEST_KEY": "1"}
        assert os.environ["REPRO_TEST_KEY"] == "1"
        assert envmod.apply({"REPRO_TEST_KEY": "1"}) == {}   # idempotent
        monkeypatch.delenv("REPRO_TEST_KEY")

    def test_xla_flags_after_backend_init_warns(self, monkeypatch):
        import jax

        jax.devices()                        # force backend init
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        with pytest.warns(UserWarning, match="backend init"):
            envmod.apply({"XLA_FLAGS": "--xla_foo=1"})
        monkeypatch.delenv("XLA_FLAGS", raising=False)


class TestArgparseGlue:
    def _parse(self, argv):
        ap = argparse.ArgumentParser()
        envmod.add_env_args(ap)
        return ap.parse_args(argv)

    def test_defaults_are_noop(self, monkeypatch):
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        args = self._parse([])
        assert envmod.apply_env_args(args) == {}
        assert "XLA_FLAGS" not in os.environ

    def test_missing_tcmalloc_warns_not_raises(self, monkeypatch):
        args = self._parse(["--tcmalloc"])
        monkeypatch.setattr(envmod, "find_tcmalloc", lambda: None)
        with pytest.warns(UserWarning, match="libtcmalloc"):
            envmod.apply_env_args(args)


class TestCompileCache:
    @pytest.fixture
    def restore_cache_dir(self):
        import jax
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_dir_wins_and_nothing_is_set(self, monkeypatch, tmp_path,
                                             restore_cache_dir):
        import jax
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert envmod.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_checkout_dir(self, monkeypatch,
                                           restore_cache_dir):
        import jax
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(root, ".jax_cache")
        assert envmod.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        # idempotent: a second call names the same directory
        assert envmod.enable_compile_cache() == want

    def test_not_called_at_import(self):
        import subprocess
        import sys
        code = ("import jax; from repro.launch import env, serve, train; "
                "print(jax.config.jax_compilation_cache_dir)")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        e = {k: v for k, v in os.environ.items()
             if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
        e["PYTHONPATH"] = os.path.join(root, "src")
        e["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run([sys.executable, "-c", code], env=e,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip() == "None"
