"""The gated delta rule's chunked form against its one-step form looped (``unionml_tpu/ops/delta_rule.py``), on the
CPU in float32: the two are one function, so they differ by summation order and the triangular solve alone, 2e-5 on
outputs of unit scale (1e-4 where every decay sits at the bound and the sub-block factors reach e^80). The step's
Pallas kernel, in interpret mode, against the step: summation order alone, 1e-5 after 32 chained steps."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.models.moe import route_top_k
from unionml_tpu.ops.delta_rule import delta_rule_chunked, delta_rule_step, delta_rule_step_kernel

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


#: the one-token step's two forms: XLA's, and the kernel in interpret mode over blocks of one head (H = 3)
STEPS = {"step": delta_rule_step, "step_kernel": functools.partial(delta_rule_step_kernel, block_heads=1, interpret=True)}


@pytest.mark.parametrize("form", ["step", "step_kernel", "chunked"])
def test_a_masked_row_keeps_its_state_bit_for_bit(form):
    """Row 0 masked (``beta = 0``, ``g = 0``), row 1 live: row 0's state comes back bit for bit, row 1's moves."""
    state, q, k, v, g, beta, _ = inputs(1, 9)
    live = jnp.arange(B) > 0
    if form == "chunked":
        _, kept = delta_rule_chunked(state, q, k, v, g, beta, jnp.broadcast_to(live[:, None], (B, 1)))
    else:
        masked_g, masked_beta = jnp.where(live[:, None, None], g[:, 0], 0.0), jnp.where(live[:, None], beta[:, 0], 0.0)
        _, kept = STEPS[form](state, q[:, 0], k[:, 0], v[:, 0], masked_g, masked_beta)
    assert (np.asarray(kept[0]) == np.asarray(state[0])).all()
    assert not np.allclose(np.asarray(kept[1]), np.asarray(state[1]))


KB, KH, KD = 3, 4, 128  # the kernel's tests: whole 128 x 128 tiles, blocks of 2 heads, so the grid is 3 x 2


def kernel_inputs(seed, bound):
    """A non-zero state and one token a step for 32 steps, as the layer makes them (unit keys, queries scaled by
    ``d_k ** -0.5``); every log-decay at the bound -5 where ``bound``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (32, KB, KH, KD))) * KD ** -0.5
    k = unit(jax.random.normal(ks[1], (32, KB, KH, KD)))
    v = jax.random.normal(ks[2], (32, KB, KH, KD))
    g = jnp.full((32, KB, KH, KD), -5.0) if bound else -5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(ks[3], (32, KB, KH, KD)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (32, KB, KH)))
    return jax.random.normal(ks[5], (KB, KH, KD, KD)), (q, k, v, g, beta)


@pytest.mark.parametrize("bound", [False, True], ids=["decays_drawn", "decays_at_bound"])
def test_the_kernel_equals_the_step_over_32_chained_steps(bound):
    state, tokens = kernel_inputs(36, bound)
    kernel = functools.partial(delta_rule_step_kernel, block_heads=2, interpret=True)

    def chained(step):
        def body(s, xs):
            out, s = step(s, *xs)
            return s, out

        return jax.jit(lambda s: jax.lax.scan(body, s, tokens))(state)

    want_state, want = chained(delta_rule_step)
    got_state, got = chained(kernel)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_state), np.asarray(want_state), atol=1e-5)


def test_the_kernel_updates_the_state_in_place():
    """The state's input is its output's buffer: the kernel is called with the alias, and under ``jit`` with the
    state donated the returned state is the donated buffer, not a copy beside it."""
    state, (q, k, v, g, beta) = kernel_inputs(37, False)
    step = functools.partial(delta_rule_step_kernel, block_heads=2, interpret=True)
    args = (state, q[0], k[0], v[0], g[0], beta[0])
    (call,) = [e for e in jax.make_jaxpr(step)(*args).jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["input_output_aliases"] == ((0, 1),)
    state = jnp.array(state)
    buffer = state.unsafe_buffer_pointer()
    _, updated = jax.jit(step, donate_argnums=0)(state, *args[1:])
    assert state.is_deleted() and updated.unsafe_buffer_pointer() == buffer


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
