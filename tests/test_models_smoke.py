"""Per-arch smoke tests (assignment requirement): instantiate the REDUCED
variant of each family and run one forward/train step on CPU, asserting
output shapes and no NaNs.

The heaviest (arch, test) pairs are marked ``slow`` (see pyproject
``addopts``) so the default suite keeps one fast representative per family:
llama3/qwen3/starcoder2 (dense), granite (MoE), mamba2 (ssm).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import arch_cases

from repro.configs import ARCHITECTURES
from repro.models import FRONTEND_DIM, Model
from repro.models.layers import pad_vocab
from repro.training.optimizer import AdamWConfig, adamw_init, adamw_update

SLOW_TRAIN = (
    "deepseek-v2-236b", "jamba-v0.1-52b", "grok-1-314b", "pixtral-12b",
    "seamless-m4t-large-v2",
)


def make_batch(cfg, B=2, S=32, rng=None):
    rng = rng or np.random.default_rng(0)
    if cfg.is_encdec or cfg.frontend:
        return {
            "features": jnp.asarray(
                rng.normal(size=(B, S // 2, FRONTEND_DIM)), jnp.bfloat16
            ),
            "tokens": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (B, S // 2)), jnp.int32
            ),
            "labels": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (B, S // 2)), jnp.int32
            ),
        }
    return {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
    }


@pytest.mark.parametrize("name", arch_cases(("deepseek-v2-236b",)))
def test_forward_shapes_no_nans(name, model_bank):
    cfg = ARCHITECTURES[name].reduced()
    model, params = model_bank(cfg)
    B, S = 2, 32
    batch = make_batch(cfg, B, S)
    logits, aux, _ = model.forward(params, batch)
    seq = batch["tokens"].shape[1] + (
        batch["features"].shape[1] if (cfg.frontend and not cfg.is_encdec) else 0
    )
    assert logits.shape == (B, seq, pad_vocab(cfg.vocab_size))
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())
    assert not bool(jnp.isnan(aux))


@pytest.mark.parametrize("name", arch_cases(SLOW_TRAIN))
def test_one_train_step(name, model_bank):
    cfg = ARCHITECTURES[name].reduced()
    model, params = model_bank(cfg, remat=True)
    opt = adamw_init(params)
    batch = make_batch(cfg)
    loss0 = model.loss(params, batch)
    grads = jax.grad(lambda p: model.loss(p, batch))(params)
    new_params, new_opt, gnorm = adamw_update(AdamWConfig(), grads, opt, params)
    assert not bool(jnp.isnan(loss0)) and float(loss0) > 0
    assert float(gnorm) > 0 and not bool(jnp.isnan(gnorm))
    # parameters actually changed
    diffs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))),
        params, new_params,
    )
    assert max(jax.tree.leaves(diffs)) > 0


@pytest.mark.parametrize("name", arch_cases())
def test_decode_step_shapes(name, model_bank):
    cfg = ARCHITECTURES[name].reduced()
    model, params = model_bank(cfg)
    B, W = 2, 16
    caches = model.init_cache(B, W)
    lengths = jnp.full((B,), W, jnp.int32)  # steady-state ring
    toks = jnp.ones((B, 1), jnp.int32)
    logits, new_caches, new_len = model.decode_step(params, caches, toks, lengths)
    assert logits.shape == (B, pad_vocab(cfg.vocab_size))
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())
    assert jax.tree.structure(caches) == jax.tree.structure(new_caches)
    assert (np.asarray(new_len) == W + 1).all()


@pytest.mark.parametrize("name", arch_cases())
def test_init_bit_identical_to_eager(name):
    """Per-leaf-jitted init must reproduce the eager init bit for bit: the
    process backend and the chip smoke rebuild params from a seed and
    compare tokens against params built in another process."""
    from repro.models import schema as sch

    model = Model(ARCHITECTURES[name].reduced())
    schema = model.schema()
    rng = jax.random.key(3)
    got = model.init(rng)
    leaves, treedef = jax.tree.flatten(schema, is_leaf=sch.is_spec)
    keys = jax.random.split(rng, len(leaves))
    want = jax.tree.unflatten(
        treedef,
        [sch._init_leaf(k, s, model.dtype) for k, s in zip(keys, leaves)],
    )
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(np.asarray(g).view(np.uint16),
                              np.asarray(w).view(np.uint16))
