"""Launchers: depth/batch cuts at published widths, in-process ``main(argv)``
calls, and where the persistent compilation cache lands."""
import math
import os
import pathlib
import subprocess
import sys

import pytest

from repro.configs import SHAPES_BY_NAME, get_arch
from repro.launch import serve, train
from repro.launch.cache import CHECKOUT_CACHE_DIR

REPO = pathlib.Path(__file__).parent.parent


@pytest.mark.parametrize("arch,periods,batch,dp,layers", [
    ("gemma3-1b", 1, 1, 1, 6), ("deepseek-7b", 2, 4, 1, 2),
    ("gemma3-1b", 4, 2, 2, 24),
])
def test_cut_to_size_keeps_widths(arch, periods, batch, dp, layers):
    full = get_arch(arch)
    cfg, shape, cuts = train.cut_to_size(
        full, SHAPES_BY_NAME["train_4k"], periods=periods, batch=batch,
        dp=dp)
    assert cfg.num_layers == layers
    assert cfg.layer_kinds() == full.layer_kinds()[:layers]
    assert (cfg.d_model, cfg.d_ff, cfg.num_heads, cfg.vocab_size) == \
        (full.d_model, full.d_ff, full.num_heads, full.vocab_size)
    assert shape.global_batch == batch * dp and shape.seq_len == 4096
    assert len(cuts) == 2


def test_cut_to_size_rejects_more_periods_than_the_model_has():
    with pytest.raises(ValueError, match="has 26"):
        train.cut_to_size(get_arch("gemma3-1b"), SHAPES_BY_NAME["train_4k"],
                          periods=5, batch=0, dp=1)


def test_train_main_runs_in_process(tmp_path, monkeypatch):
    # a set variable keeps the helper from touching this process's config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    trainer, state = train.main(["--arch", "gemma3-1b", "--smoke",
                                 "--periods", "1", "--batch", "2",
                                 "--steps", "2"])
    assert trainer.arch.num_layers == 6 and trainer.shape.global_batch == 2
    assert [h["step"] for h in trainer.history] == [0, 1]
    assert all(math.isfinite(h["loss"]) for h in trainer.history)
    assert int(state["step"]) == 2


def test_serve_main_runs_in_process(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    eng, reqs = serve.main(["--arch", "gemma3-1b", "--smoke", "--requests",
                            "2", "--prompt-len", "8", "--max-new", "3"])
    assert [len(r.out_tokens) for r in reqs] == [3, 3]
    assert all(r.done for r in reqs)


_PROBE = """
import jax
from repro.launch.cache import init_compile_cache
d = init_compile_cache()
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 3 + 1)(2.0).block_until_ready()
print(d)
print(jax.config.jax_compilation_cache_dir)
"""


def _probe(env_dir, compile_):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c",
                          _PROBE.format(compile=compile_)],
                         capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_compile_cache_lands_in_the_environment_dir(tmp_path):
    cache = tmp_path / "cache"
    returned, configured = _probe(str(cache), True)
    assert returned == configured == str(cache)
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_the_checkout():
    returned, configured = _probe(None, False)
    assert returned == configured == str(CHECKOUT_CACHE_DIR)
    assert CHECKOUT_CACHE_DIR == REPO / ".jax_cache"
