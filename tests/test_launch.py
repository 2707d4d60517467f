"""Entry-point plumbing: compile-cache placement and the serve launcher."""

import jax
import numpy as np
import pytest

from repro.launch import compile_cache, serve


@pytest.mark.parametrize("env_dir", ["/elsewhere/jax-cache", None])
def test_compile_cache_placement(monkeypatch, env_dir):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it and the helper sets
    nothing; without it, the cache goes to the fixed checkout directory."""
    before = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv(compile_cache.ENV, env_dir)
    else:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
    try:
        got = compile_cache.use_compile_cache()
        if env_dir:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got.endswith(".jax_cache")
            assert (compile_cache.CHECKOUT_CACHE.parent / "pyproject.toml").exists()
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serve_main_returns_its_run(monkeypatch, capsys):
    """``serve.main(argv)`` serves mixed prompt lengths after warming up
    and hands back the requests with every generated token."""
    monkeypatch.setattr(serve, "use_compile_cache", lambda: "(test: off)")
    run = serve.main([
        "--arch", "starcoder2-3b", "--reduced", "--clients", "2",
        "--requests", "2", "--prompt-len", "5", "--prompt-len-max", "30",
        "--new-tokens", "3", "--max-batch", "2",
    ])
    assert len(run.requests) == 4
    assert len(run.engine.store.records) == 4
    assert all(len(r.generated) == 3 for r in run.requests)
    lens = {len(r.prompt_tokens) for r in run.requests}
    assert len(lens) > 1 and min(lens) >= 5 and max(lens) <= 30
    assert run.engine.warmup and run.engine.warm_s > 0
    assert np.all(np.concatenate([r.generated for r in run.requests])
                  < run.model.cfg.vocab_size)
    out = capsys.readouterr().out
    assert "requests answered: 4 of 4" in out and "measured" in out
