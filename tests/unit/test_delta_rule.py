"""The gated delta rule's chunked form against its one-step form looped (``unionml_tpu/ops/delta_rule.py``), on the
CPU in float32: the two are one function, so they differ by summation order and the triangular solve alone, 2e-5 on
outputs of unit scale (1e-4 where every decay sits at the bound and the sub-block factors reach e^80)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.models.moe import route_top_k
from unionml_tpu.ops.delta_rule import delta_rule_chunked, delta_rule_step

B, H, DK, DV = 2, 3, 16, 8


def inputs(length, seed, bound=False, zero_state=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = jax.random.normal(ks[0], (B, length, H, DK))
    k = jax.random.normal(ks[1], (B, length, H, DK))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, length, H, DV))
    g = -5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(ks[3], (B, length, H, DK)))
    if bound:
        g = jnp.full_like(g, -5.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, length, H)))
    state = jnp.zeros((B, H, DK, DV)) if zero_state else jax.random.normal(ks[5], (B, H, DK, DV))
    return state, q, k, v, g, beta, ks[6]


def looped(state, q, k, v, g, beta, mask):
    outs = []
    for t in range(q.shape[1]):
        live = mask[:, t]
        out, state = delta_rule_step(
            state, q[:, t], k[:, t], v[:, t], jnp.where(live[:, None, None], g[:, t], 0.0), jnp.where(live[:, None], beta[:, t], 0.0)
        )
        outs.append(out)
    return jnp.stack(outs, axis=1), state


@pytest.mark.parametrize("masked", ["none", "inside_and_end"])
@pytest.mark.parametrize("length,bound,zero_state", [(37, False, False), (64, True, False), (150, False, False), (200, True, True)],
                         ids=["short", "one_chunk_at_bound", "ragged", "ragged_at_bound_from_zero"])
def test_chunked_equals_the_step_looped(length, bound, zero_state, masked):
    """Lengths that are no multiple of 64, a non-zero starting state, masked positions inside and at the end (they
    leave the state untouched), and every log-decay at the bound -5: the factorised products' worst case."""
    state, q, k, v, g, beta, key = inputs(length, length, bound, zero_state)
    mask = jnp.ones((B, length), bool)
    if masked != "none":
        mask = (jax.random.uniform(key, (B, length)) > 0.2).at[:, -5:].set(False)
    want, want_state = looped(state, q, k, v, g, beta, mask)
    got, got_state = jax.jit(delta_rule_chunked)(state, q, k, v, g, beta, mask)
    tol = 1e-4 if bound else 2e-5
    live = np.asarray(mask)[:, :, None, None]
    np.testing.assert_allclose(np.asarray(got) * live, np.asarray(want) * live, atol=tol)
    np.testing.assert_allclose(np.asarray(got_state), np.asarray(want_state), atol=tol)
    assert np.isfinite(np.asarray(got)).all()


def test_a_masked_row_keeps_its_state_bit_for_bit():
    state, q, k, v, g, beta, _ = inputs(1, 9)
    _, kept = delta_rule_step(state, q[:, 0], k[:, 0], v[:, 0], jnp.zeros_like(g[:, 0]), jnp.zeros_like(beta[:, 0]))
    assert (np.asarray(kept) == np.asarray(state)).all()
    _, kept = delta_rule_chunked(state, q, k, v, g, beta, jnp.zeros((B, 1), bool))
    assert (np.asarray(kept) == np.asarray(state)).all()


def test_the_layer_refuses_a_decay_bound_the_sub_blocks_cannot_carry():
    """``exp(SUB * -bound)`` must stay a float32: the layer, which owns the bound, says so before any state is made."""
    from unionml_tpu.models.layers import KimiDeltaAttention

    x = jnp.zeros((1, 3, 8), jnp.float32)
    KimiDeltaAttention(n_heads=2, head_dim=4, decay_bound=-5.5, dtype=jnp.float32).init(jax.random.PRNGKey(0), x)
    for bound in (-5.6, 0.1):
        with pytest.raises(ValueError, match="decay_bound"):
            KimiDeltaAttention(n_heads=2, head_dim=4, decay_bound=bound, dtype=jnp.float32).init(jax.random.PRNGKey(0), x)


# ------------------------------------------------------------------ group-limited routing (models/moe.py)


def test_one_routing_group_is_the_ungrouped_rule_bit_for_bit():
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(0), (33, 16)))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    plain, _ = jax.lax.top_k(scores + bias, 4)
    chosen, weights = route_top_k(scores, bias, 4, scale=2.5)
    grouped_chosen, grouped_weights = route_top_k(scores, bias, 4, scale=2.5, n_group=1, topk_group=1)
    assert (np.asarray(chosen) == np.asarray(grouped_chosen)).all() and (np.asarray(weights) == np.asarray(grouped_weights)).all()
    assert (np.sort(np.asarray(jnp.take_along_axis(scores + bias, chosen, axis=-1)))[:, ::-1] == np.asarray(plain)).all()


@pytest.mark.parametrize("n_group,topk_group,k", [(4, 2, 3), (8, 4, 8), (2, 1, 2)])
def test_group_limited_routing_against_a_plain_loop(n_group, topk_group, k):
    """Token by token in numpy: a group scores the sum of its two largest biased scores, the best groups stay, the
    top-k is taken among their experts, and the weights are the unbiased scores normalised over the chosen."""
    n_experts = 32
    scores = np.asarray(jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(2), (41, n_experts))))
    bias = np.asarray(0.05 * jax.random.normal(jax.random.PRNGKey(3), (n_experts,)))
    chosen, weights = route_top_k(jnp.asarray(scores), jnp.asarray(bias), k, scale=2.5, n_group=n_group, topk_group=topk_group)
    size = n_experts // n_group
    for t in range(scores.shape[0]):
        biased = scores[t] + bias
        group_scores = [np.sort(biased[g * size : (g + 1) * size])[-2:].sum() for g in range(n_group)]
        stay = np.argsort(group_scores)[-topk_group:]
        allowed = [e for g in stay for e in range(g * size, (g + 1) * size)]
        want = sorted(allowed, key=lambda e: -biased[e])[:k]
        assert sorted(np.asarray(chosen[t]).tolist()) == sorted(want)
        picked = scores[t][np.asarray(chosen[t])]
        np.testing.assert_allclose(np.asarray(weights[t]), picked / picked.sum() * 2.5, rtol=1e-6)
