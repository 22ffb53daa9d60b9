"""The pieces that put the serving path on a chip: published Qwen3-0.6B
widths, full-width engine sizing, per-engine device placement, the
compile-cache location, the explicit kernel backend choice, and
``chip_smoke.py`` refusing to run without a TPU."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.configs import get_config, get_reduced_config
from repro.kernels import ops
from repro.launch import compile_cache
from repro.launch.serve import engine_kw

REPO = Path(__file__).resolve().parents[1]


def _run(code: str, **env) -> subprocess.CompletedProcess:
    """Run ``code`` in a fresh interpreter, so flags that JAX reads at
    import take effect."""
    full_env = dict(os.environ, PYTHONPATH=str(REPO / "src"), **env)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          cwd=REPO, env=full_env, capture_output=True,
                          text=True, timeout=600)


def test_qwen3_0_6b_is_the_published_config():
    cfg = get_config("qwen3-0.6b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim) == (28, 1024, 16, 8, 128)
    assert "Qwen/Qwen3-0.6B" in cfg.source
    # the CPU variant recomputes head_dim from its own width
    small = get_reduced_config("qwen3-0.6b")
    assert small.head_dim == small.d_model // small.n_heads


def test_engine_kw_sizes_pool_from_kv_budget():
    cfg = get_config("qwen3-0.6b")
    kw = engine_kw(cfg, kv_gb=2.0)
    # 28 layers * (k + v) * 8 KV heads * 128 * 2 bytes = 112 KiB per token
    page_bytes = 112 * 1024 * kw["page_size"]
    assert kw["num_pages"] == (2 << 30) // page_bytes - 1
    assert kw["dtype"] == "bfloat16"
    with pytest.raises(ValueError):
        engine_kw(cfg, kv_gb=1e-6)


def test_runner_and_build_engines_place_on_devices():
    """Four virtual CPU devices: ModelRunner(device=...) commits params,
    pool and LoRA bank there and serves a request; build_engines puts
    engine i on device i with one shared set of weights."""
    res = _run("""
        import jax, numpy as np
        from repro.configs import get_reduced_config
        from repro.engine import EngineConfig, InferenceEngine, Request, \\
            SamplingParams
        from repro.launch.serve import REDUCED_ENGINE_KW, build_engines

        devs = jax.devices()
        assert len(devs) == 4, devs
        cfg = get_reduced_config("qwen3-0.6b")
        kw = dict(REDUCED_ENGINE_KW, num_pages=32)

        def placed(eng, dev):
            r = eng.runner
            return all(x.devices() == {dev} for x in
                       jax.tree.leaves((r.params, r.pool, r.lora)))

        eng = InferenceEngine(cfg, EngineConfig(**kw), device=devs[2])
        assert placed(eng, devs[2])
        req = Request(prompt_tokens=list(range(1, 20)),
                      sampling=SamplingParams(max_new_tokens=3))
        eng.submit(req)
        eng.run_until_idle()
        assert len(req.output_tokens) == 3
        assert placed(eng, devs[2])          # the step kept the pool there

        engines, _, _ = build_engines(cfg, ["mixed"] * 4, lambda: 0.0, kw)
        for i, e in enumerate(engines.values()):
            assert placed(e, devs[i]), i
        w = [np.asarray(e.params["embedding"]) for e in engines.values()]
        assert all((x == w[0]).all() for x in w)    # one model, 4 replicas
        print("PLACED_OK")
        """, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert res.returncode == 0, res.stderr[-3000:]
    assert "PLACED_OK" in res.stdout


def test_chip_smoke_refuses_cpu():
    res = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert "needs a TPU" in res.stderr
    lines = res.stdout.strip().splitlines()
    assert not lines or '"ok"' not in lines[-1]


def test_smoke_experiments_refuse_cpu():
    res = subprocess.run(
        [sys.executable, str(REPO / "tools" / "smoke_experiments.py"),
         "schedule"], cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert "needs a TPU" in res.stderr
    assert "schedule:" not in res.stdout


def test_compile_cache_follows_env(monkeypatch):
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before   # left to JAX


def test_compile_cache_default_is_fixed_repo_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.compile_cache_dir() == str(REPO / ".jax_cache")


@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False)])
def test_kernel_mode_follows_backend(monkeypatch, backend, interpret):
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    assert ops._interpret() is interpret


def test_kernels_refuse_other_backends(monkeypatch):
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret()

