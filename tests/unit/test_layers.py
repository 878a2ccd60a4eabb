"""Layer-level oracles for the SPMD-clean embedding lookup.

``IotaEmbed`` (unionml_tpu/models/layers.py) must be a drop-in for
``nn.Embed``: identical param tree, bit-identical lookups (gather forward),
and gradients numerically equal to the scatter-add backward — only the
MECHANISM differs (one-hot matmul, which the SPMD partitioner can
reduce-scatter into a vocab-sharded table; the multichip dryrun asserts the
resulting warning-free partitioner log).
"""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax import linen as nn

from unionml_tpu.models.layers import IotaEmbed, _embed_lookup

VOCAB, DIM = 37, 16


@pytest.fixture
def table():
    return jax.random.normal(jax.random.PRNGKey(0), (VOCAB, DIM), jnp.float32)


def test_forward_is_bit_identical_to_take(table):
    tokens = jnp.asarray([[0, 3, 36, 3], [7, 7, 1, 0]], jnp.int32)
    ours = _embed_lookup(table, tokens, VOCAB)
    ref = jnp.take(table, tokens, axis=0)
    assert (ours == ref).all()


def test_backward_matches_scatter_add(table):
    tokens = jnp.asarray([[2, 5, 5, 11], [5, 0, 2, 2]], jnp.int32)
    cot = jax.random.normal(jax.random.PRNGKey(1), (2, 4, DIM), jnp.float32)

    def ours(t):
        return (_embed_lookup(t, tokens, VOCAB) * cot).sum()

    def ref(t):
        return (jnp.take(t, tokens, axis=0) * cot).sum()

    g_ours = jax.grad(ours)(table)
    g_ref = jax.grad(ref)(table)
    # repeated tokens accumulate; untouched rows stay exactly zero
    np.testing.assert_allclose(np.asarray(g_ours), np.asarray(g_ref), atol=1e-5)
    untouched = sorted(set(range(VOCAB)) - {0, 2, 5, 11})
    assert not np.asarray(g_ours)[untouched].any()


def test_module_param_tree_matches_nn_embed():
    tokens = jnp.zeros((1, 4), jnp.int32)
    ours = IotaEmbed(VOCAB, DIM, dtype=jnp.float32, param_dtype=jnp.float32)
    ref = nn.Embed(VOCAB, DIM, dtype=jnp.float32, param_dtype=jnp.float32)
    p_ours = ours.init(jax.random.PRNGKey(2), tokens)["params"]
    p_ref = ref.init(jax.random.PRNGKey(2), tokens)["params"]
    assert set(p_ours) == set(p_ref) == {"embedding"}
    assert p_ours["embedding"].shape == p_ref["embedding"].shape
    # same init distribution family and seed -> same values (drop-in for
    # checkpoints written against nn.Embed)
    np.testing.assert_allclose(
        np.asarray(p_ours["embedding"]), np.asarray(p_ref["embedding"]), atol=0
    )
    # lookups agree module-to-module
    toks = jnp.asarray([[1, 4, 9, 25]], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(ours.apply({"params": p_ours}, toks)),
        np.asarray(ref.apply({"params": p_ref}, toks)),
    )


def test_bf16_grad_dtype_follows_operand():
    table16 = jax.random.normal(jax.random.PRNGKey(3), (VOCAB, DIM), jnp.float32)
    tokens = jnp.asarray([[1, 2]], jnp.int32)

    def loss(t):
        return _embed_lookup(t.astype(jnp.bfloat16), tokens, VOCAB).astype(jnp.float32).sum()

    g = jax.grad(loss)(table16)
    assert g.dtype == jnp.float32  # the astype backward restores param dtype
    assert bool(jnp.isfinite(g).all())


# --------------------------------------------------------------------------- Attention's options, RMSNorm's epsilon


def _attention(**options):
    from unionml_tpu.models.layers import Attention

    return Attention(n_heads=4, n_kv_heads=2, head_dim=8, causal=True, rope=True, dtype=jnp.float32, **options)


def test_attention_options_default_off_and_add_no_parameter():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 16), jnp.float32)
    plain = _attention().init(jax.random.PRNGKey(1), x)["params"]
    assert set(plain) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    full = _attention(window=4, qk_norm=True, gated=True).init(jax.random.PRNGKey(1), x)["params"]
    assert set(full) == set(plain) | {"q_norm", "k_norm", "gate_proj"}
    assert full["q_norm"]["scale"].shape == (8,) and full["gate_proj"]["kernel"].shape == (16, 32)
    # a window no query can outgrow changes nothing
    wide = _attention(window=12).apply({"params": plain}, x)
    np.testing.assert_allclose(np.asarray(wide), np.asarray(_attention().apply({"params": plain}, x)), atol=1e-6)


@pytest.mark.parametrize("window", [1, 3, 5])
def test_attention_window_masks_every_read_alike(window):
    """The uncached forward under a window, and the same tokens fed one at a time through the contiguous cache
    and through a paged one (the gather read): key j is visible to query i iff i - window < j <= i."""
    from unionml_tpu.models.generate import init_cache, init_paged_cache

    layer = _attention(window=window, qk_norm=True, gated=True)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 10, 16), jnp.float32)
    params = layer.init(jax.random.PRNGKey(3), x)["params"]
    whole = layer.apply({"params": params}, x)
    narrower = _attention(window=window + 4, qk_norm=True, gated=True).apply({"params": params}, x)
    assert float(jnp.abs(whole[:, -1] - narrower[:, -1]).max()) > 1e-4  # the window binds at the last position
    shape = types.SimpleNamespace(n_layers=1, n_kv_heads=2, head_dim=8, dim=16, n_heads=4, dtype=jnp.float32)
    paged = init_paged_cache(shape, 1, 6, 4, 3, fill_block=5)[0]
    paged["table"] = jnp.asarray([[2, 0, 4]], jnp.int32)
    for cache in (init_cache(shape, 1, 12)[0], paged):
        rows = []
        for t in range(10):
            out, cache = layer.apply({"params": params}, x[:, t : t + 1], jnp.asarray([[t]]), None, cache)
            rows.append(out)
        np.testing.assert_allclose(np.asarray(jnp.concatenate(rows, axis=1)), np.asarray(whole), atol=1e-5)


def test_init_cache_takes_the_configurations_own_head_width():
    from unionml_tpu.models.generate import init_cache, init_paged_cache

    stated = types.SimpleNamespace(n_layers=2, n_kv_heads=2, head_dim=32, dim=64, n_heads=4, dtype=jnp.bfloat16)
    derived = types.SimpleNamespace(n_layers=2, n_kv_heads=2, dim=64, n_heads=4, dtype=jnp.bfloat16)
    assert init_cache(stated, 1, 8)[0]["k"].shape == (1, 8, 2, 32) and init_cache(derived, 1, 8)[0]["k"].shape == (1, 8, 2, 16)
    assert init_paged_cache(stated, 1, 3, 4, 2, fill_block=2)[1]["v"].shape == (2, 3, 4, 32)


@pytest.mark.parametrize("epsilon", [1e-6, 1e-5, 1e-2])
def test_rms_norm_epsilon_is_an_option(epsilon):
    from unionml_tpu.models.layers import RMSNorm

    x = jnp.full((1, 4), 1e-3, jnp.float32)  # small enough for the epsilon to show
    norm = RMSNorm(epsilon=epsilon, dtype=jnp.float32)
    out = norm.apply(norm.init(jax.random.PRNGKey(0), x), x)
    np.testing.assert_allclose(np.asarray(out), 1e-3 / np.sqrt(1e-6 + epsilon), rtol=1e-5)
    assert RMSNorm().epsilon == 1e-6  # the default every existing model runs with
