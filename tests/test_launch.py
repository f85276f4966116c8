"""The launch path: the serving launcher's width/impl knobs, the
persistent compilation cache it turns on, and ``chip_smoke.py``'s
refusal to run without a TPU."""
import os
import subprocess
import sys
from pathlib import Path

from repro.configs import get_arch
from repro.launch.compile_cache import CHECKOUT_CACHE_DIR
from repro.launch.serve import run_serving, serving_config

ROOT = Path(__file__).resolve().parents[1]


def _run(code_or_argv, env_extra, *, drop=(), timeout=300):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.update(env_extra)
    argv = code_or_argv if isinstance(code_or_argv, list) \
        else [sys.executable, "-c", code_or_argv]
    return subprocess.run(argv, env=env, cwd=ROOT, capture_output=True,
                          text=True, timeout=timeout)


def test_chip_smoke_refuses_without_tpu():
    r = _run([sys.executable, str(ROOT / "chip_smoke.py")], {})
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_serving_config_full_width_is_the_registered_config():
    cfg = serving_config("qwen2.5-3b", full_width=True,
                         attention_impl="pallas")
    assert cfg == get_arch("qwen2.5-3b").replace(attention_impl="pallas")
    small = serving_config("qwen2.5-3b")
    assert (small.d_model, small.num_layers) == (128, 2)


def test_run_serving_attention_impls():
    """Both impls serve every request, and on the CPU (f32 matmuls in
    both) the Pallas path reproduces the XLA path's greedy tokens: the
    model's pallas decode writes and reads the cache as the xla one does."""
    tokens = {impl: [c.tokens for c in run_serving(
        "qwen2.5-3b", num_requests=4, max_new=6, attention_impl=impl,
        sampler="greedy", log_fn=lambda *_: None)]
        for impl in ("xla", "pallas")}
    assert [len(t) for t in tokens["xla"]] == [6] * 4
    assert tokens["pallas"] == tokens["xla"]


_COMPILE = (
    "import jax, jax.numpy as jnp\n"
    "from repro.launch.compile_cache import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n")


def test_compile_cache_env_dir_is_used_and_nothing_else(tmp_path):
    before = sorted(CHECKOUT_CACHE_DIR.glob("*")) \
        if CHECKOUT_CACHE_DIR.exists() else []
    cache = tmp_path / "cc"
    r = _run(_COMPILE, {"JAX_COMPILATION_CACHE_DIR": str(cache),
                        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(cache)] * 2
    assert any(cache.iterdir())
    after = sorted(CHECKOUT_CACHE_DIR.glob("*")) \
        if CHECKOUT_CACHE_DIR.exists() else []
    assert after == before


def test_compile_cache_defaults_to_the_ignored_checkout_dir():
    code = _COMPILE.rsplit("jax.jit", 1)[0]      # resolve only, no compile
    r = _run(code, {}, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(ROOT / ".jax_cache")] * 2
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
