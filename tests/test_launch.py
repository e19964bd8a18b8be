"""Launch plumbing: where the compile cache lands, that a TPU never takes
the jnp reference behind the caller's back, that every Pallas entry point
is told its route, the serving CLI, and the dry-run's XLA_FLAGS."""
import glob
import inspect
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.launch import compile_cache

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == compile_cache.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == compile_cache.CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert os.path.dirname(compile_cache.CACHE_DIR) == ROOT
    with open(os.path.join(ROOT, ".gitignore")) as f:
        ignored = f.read().split()
    assert os.path.basename(compile_cache.CACHE_DIR) + "/" in ignored


_CACHE_CHILD = textwrap.dedent("""
    import os, sys
    import jax, jax.numpy as jnp
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.launch import compile_cache
    where = compile_cache.enable_compile_cache()
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
    print("DIR", where, jax.config.jax_compilation_cache_dir)
""")


def test_compile_cache_env_dir_is_where_it_lands(tmp_path):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", _CACHE_CHILD], env=env,
                         capture_output=True, text=True, timeout=300)
    assert f"DIR {tmp_path} {tmp_path}" in out.stdout, out.stderr[-2000:]
    assert os.listdir(tmp_path), "no cache entry written"
    assert not os.path.exists(os.path.join(str(tmp_path), ".jax_cache"))


@pytest.mark.parametrize("seeded", [False, True])
def test_spinner_without_kernel_raises_on_tpu(monkeypatch, seeded):
    """On TPU a spinner the kernel cannot run (ldr) raises; asking for the
    reference explicitly still works."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    n, m = 16, 32
    x = jnp.ones((3, n), jnp.float32)
    if seeded:
        def call(up):
            return ops.spinner_project_seeded("ldr", jnp.uint32(1), x, m,
                                              use_pallas=up)
    else:
        from repro.kernels import seedgen
        params = seedgen.seeded_params("ldr", n, m, jnp.uint32(1))

        def call(up):
            return ops.spinner_project("ldr", params, x, m, use_pallas=up)
    with pytest.raises(ValueError, match="no compiled TPU kernel"):
        call(None)
    assert call(False).shape == (3, m)


def test_pallas_entry_points_take_the_route_explicitly():
    import importlib
    entry = {"spinner": ("spinner_project_pallas",
                         "spinner_project_seeded_pallas"),
             "srf_decode": ("srf_decode_pallas",),
             "paged_gather": ("paged_gather_pallas",
                              "paged_gather_dequant_pallas"),
             "fwht": ("fwht_pallas",),
             "circulant": ("circulant_project_pallas",)}
    for mod, names in entry.items():
        m = importlib.import_module(f"repro.kernels.{mod}")
        for name in names:
            fn = inspect.unwrap(getattr(m, name))
            p = inspect.signature(fn).parameters["interpret"]
            assert p.default is inspect.Parameter.empty, name


def test_serve_cli_reduced_opt_in(monkeypatch, tmp_path):
    from repro.launch import serve
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert serve.main(["--arch", "qwen3-4b", "--reduced", "--requests", "2",
                       "--prompt-len", "6", "--max-new", "3",
                       "--max-len", "32"]) == 0


def test_serve_cli_device_trace(monkeypatch, tmp_path, capsys):
    from repro.launch import serve
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    assert serve.main(["--arch", "qwen3-4b", "--reduced", "--requests", "2",
                       "--prompt-len", "6", "--max-new", "3",
                       "--max-len", "32", "--device-trace",
                       str(tmp_path / "trace")]) == 0
    out = capsys.readouterr().out
    assert glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                     recursive=True)
    parts, = [ln for ln in out.splitlines() if "decode step ms" in ln]
    assert "attn=" in parts and "idle=" in parts
    # the CPU has no device plane: every gap is idle, named by its span
    assert "[device-trace] idle" in out


def test_dryrun_adds_to_xla_flags():
    child = ("import os; import repro.launch.dryrun; "
             "print('FLAGS', os.environ['XLA_FLAGS'])")
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_enable_fast_math=false")
    out = subprocess.run([sys.executable, "-c", child], env=env,
                         capture_output=True, text=True, timeout=300)
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("FLAGS")]
    assert line, out.stderr[-2000:]
    assert "--xla_cpu_enable_fast_math=false" in line[0]
    assert "--xla_force_host_platform_device_count=" in line[0]
