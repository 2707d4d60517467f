# NOTE: no XLA_FLAGS here on purpose — the tests see the single CPU device
# (run them with JAX_PLATFORMS=cpu); tests that need more devices start a
# subprocess with forced host devices.
import dataclasses

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def nodrop(cfg):
    """MoE variant with capacity_factor high enough that nothing drops —
    required for exact prefill/decode vs full-forward equivalence."""
    if cfg.moe is None:
        return cfg
    return dataclasses.replace(
        cfg,
        moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.n_experts) / cfg.moe.top_k
        ),
    )


@pytest.fixture(scope="session")
def model_bank():
    """Session-scoped (Model, params) cache.

    Params are shared across Model variants that don't change the schema
    (remat/unroll flags), so e.g. the forward-, decode- and train-step smoke
    tests for one architecture initialize weights once instead of three
    times. ModelConfig is a frozen dataclass, so it keys the cache directly.
    """
    import jax
    import jax.numpy as jnp

    from repro.models import Model

    models: dict = {}
    params: dict = {}

    def get(cfg, dtype=jnp.bfloat16, seed=0, **model_kw):
        mkey = (cfg, str(dtype), tuple(sorted(model_kw.items())))
        pkey = (cfg, str(dtype), seed)
        if mkey not in models:
            models[mkey] = Model(cfg, dtype=dtype, **model_kw)
        if pkey not in params:
            params[pkey] = models[mkey].init(jax.random.key(seed))
        return models[mkey], params[pkey]

    return get


@pytest.fixture(scope="session")
def engine_bank(model_bank):
    """Session-scoped warmed-ServingEngine cache, KEYED ON THE ENGINE-KNOB
    TUPLE (plus cfg/dtype/seed), so A/B tests that toggle knobs
    (packed/paged/chunked/...) re-trace each variant once per session
    instead of once per test.

    A cache hit asserts the engine drained clean and then resets its
    mutable serving state (pool state, records, store, counters) while
    KEEPING the compiled jits — the whole point of sharing. Tests that
    mutate engine structure (placement, legacy loop) or need a cold
    engine should construct their own.
    """
    import jax.numpy as jnp

    engines: dict = {}

    def get(cfg, dtype=jnp.bfloat16, seed=0, *, max_batch, max_seq,
            **engine_kw):
        from repro.serving.engine import ServingEngine

        key = (cfg, str(dtype), seed, max_batch, max_seq,
               tuple(sorted(engine_kw.items())))
        if key not in engines:
            model, params = model_bank(cfg, dtype, seed)
            engines[key] = ServingEngine(
                model, params, max_batch=max_batch, max_seq=max_seq,
                **engine_kw,
            )
            return engines[key]
        eng = engines[key]
        assert eng.idle, "engine_bank reuse requires a drained engine"
        # fresh serving state, warm jit caches
        eng.pool.reset_state()
        eng.queue.clear()
        eng._records.clear()
        eng._finished_ids.clear()
        eng._backlog_entries.clear()
        eng._prefill_finished = []
        eng._chunk_jobs.clear()
        eng._chunk_slots.clear()
        eng.store.__init__()
        if eng.prefix_reuse:
            # reset_state re-zeroed the block allocator; a stale radix
            # index would dangle references into it
            from repro.serving.prefix import RadixPrefixIndex

            eng.prefix_index = RadixPrefixIndex(eng.page)
        eng.prefill_tokens_total = 0
        eng.prefill_tokens_uncached = 0
        eng.prefill_padded_tokens = 0
        eng.prefix_hits = 0
        eng.prefix_hit_tokens = 0
        eng.decode_steps = 0
        eng.useful_steps = 0
        return eng

    return get


def arch_cases(slow_names=()):
    """Parametrize over all architectures, marking the named ones slow."""
    from repro.configs import ARCHITECTURES

    slow = set(slow_names)
    return [
        pytest.param(n, marks=pytest.mark.slow) if n in slow else n
        for n in sorted(ARCHITECTURES)
    ]
