"""The SmallThinker decoder (window and position-free global layers, a softmax-routed ReGLU expert layer on every
layer whose router reads the block's input) against the plain reference (``perf/reference/smallthinker_decoder.py``:
float32 ``jax.numpy``, one sequence, the window as a mask, every expert a dense loop), at small sizes on the CPU with
seeded weights, where the window (8) is shorter than the sequences (up to 40), so both kinds of layer matter: the
uncached forward, prefill then decode through the dense row and through the engine's paged pool, and
``ExpertShare``'s three options (softmax scores, ReGLU, a router input apart from the experts' input).

Tolerances: everything computes in float32 at ``highest`` matmul precision, so program and reference differ by
summation order alone: 2e-4 on logits of unit scale (the afmoe and GLM tests' own), 1e-5 on one layer's output,
where a module is compared with a hand-written formula of the same products."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import smallthinker_decoder as reference
from perf.systems.swa_moe_serving import module_config
from unionml_tpu.models import ExpertShare, GenerationConfig, Generator, SmallThinkerConfig, SmallThinkerTransformer
from unionml_tpu.models.layers import WINDOW_COUNTERS
from unionml_tpu.models.moe import MOE_COUNTERS
from unionml_tpu.serving import ContinuousBatcher

LAYOUT = [0, 1, 1, 1]


def config(**changes):
    """A configuration file's keys at test size: one period (global, then three windowed layers), a window of 8,
    seven query heads on one key/value head, 8 experts routed over, top-3, all held."""
    cfg = dict(
        hidden_size=64, num_attention_heads=7, num_key_value_heads=1, head_dim=16, moe_ffn_hidden_size=32, num_experts=8,
        router_experts=8, experts_first=0, moe_num_active_primary_experts=3, num_hidden_layers=4,
        sliding_window_layout=LAYOUT, rope_layout=LAYOUT, sliding_window_size=8, rope_theta=1500000.0, rms_norm_eps=1e-6,
        norm_topk_prob=True, moe_primary_router_apply_softmax=True, vocab_size=256, max_position_embeddings=128,
        precision={"compute_dtype": "float32"},
    )
    cfg.update(changes)
    return cfg


def module_for(cfg, **overrides):
    """The program's module for a configuration file's keys, by the benchmark's own mapping, in float32."""
    return SmallThinkerTransformer(module_config(cfg, param_dtype=jnp.float32, **overrides))


@pytest.fixture(scope="module")
def weights():
    return reference.make_weights(config(), 5, dtype=jnp.float32)


@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def prompt(n, seed=0):
    return np.random.default_rng(seed + n).integers(1, 256, size=n).tolist()


def reference_logits(weights, cfg, tokens, rows):
    return reference.logits_at(weights, cfg, tokens, rows, pad_to=16)


def test_the_configuration_reads_the_two_layouts_apart():
    cfg = module_config(config())
    assert cfg.sliding_window_layout == cfg.rope_layout == (0, 1, 1, 1) and cfg.experts_held == (0, 8)
    assert SmallThinkerConfig(n_layers=8).sliding_window_layout == (0, 1, 1, 1) * 2  # the published period by default
    assert module_config(config(rope_layout=[1, 1, 0, 1])).rope_layout == (1, 1, 0, 1)
    with pytest.raises(ValueError, match="sliding_window_layout"):
        SmallThinkerConfig.tiny(sliding_window_layout=(0, 1))
    with pytest.raises(ValueError, match="softmax"):
        module_config(config(moe_primary_router_apply_softmax=False))


@pytest.mark.parametrize("length", [5, 40])
def test_uncached_forward_equals_reference(weights, length):
    cfg, tokens = config(), prompt(length)
    logits = module_for(cfg).apply({"params": weights}, jnp.asarray(tokens)[None])
    np.testing.assert_allclose(np.asarray(logits[0]), reference_logits(weights, cfg, tokens, list(range(length))), atol=2e-4)


@pytest.mark.parametrize(
    "changes", [{"route_before_attention": False}, {"activation": "silu"}, {"sliding_window_layout": (0, 0, 0, 0)}],
    ids=["router_after_attention", "silu_experts", "no_window"],
)
def test_each_planted_reading_moves_the_logits(weights, changes):
    """The three faults the benchmark plants are each a different model at the test's size: the reference's
    readings are not a tolerance away from any of them."""
    cfg, tokens = config(), prompt(40)
    logits = module_for(cfg, **changes).apply({"params": weights}, jnp.asarray(tokens)[None])[0]
    assert float(jnp.max(jnp.abs(logits - reference_logits(weights, cfg, tokens, list(range(40)))))) > 1e-2


@pytest.mark.parametrize("chunk", [None, 8], ids=["one_dispatch", "chunked"])
def test_prefill_then_decode_through_the_dense_row_equals_reference(weights, chunk):
    """Generator: prompts of several lengths in one batch, right-padded, longer and shorter than the window; every
    decoded position's logits against the reference's full forward."""
    cfg, new = config(), 10
    gen = Generator(module_for(cfg), weights, GenerationConfig(max_new_tokens=new, temperature=0.0, prompt_buckets=(32,), prefill_chunk=chunk))
    prompts = [prompt(27), prompt(3), prompt(12)]
    served = gen(prompts)
    for p, out in zip(prompts, served.tolist()):
        logits = reference_logits(weights, cfg, p + out[:-1], [len(p) - 1 + i for i in range(new)])
        assert out == logits.argmax(-1).tolist()


def test_engine_over_the_paged_pool_equals_reference(weights):
    """ContinuousBatcher: chunked admission, the paste into the paged pool, decode with two rows of different
    lengths live at once past the window, and a third request that takes over a finished row's slot: each stream's
    tokens are the reference's argmax and its log-probabilities the reference's log-softmax; the engine serves the
    routing's counters and the window's under their own keys."""
    cfg = config()
    gen = Generator(module_for(cfg), weights, GenerationConfig(max_new_tokens=12, temperature=0.0, prompt_buckets=(16, 32, 48)))
    engine = ContinuousBatcher(gen, slots=2, decode_chunk=4, block_size=4, admit_chunk=16, pool_blocks=64)
    try:
        asks = [prompt(37, seed=5), prompt(5), prompt(21, seed=2)]
        streams = [(p, engine.submit(p, logprobs=True)) for p in asks]  # two slots: the third waits for one
        for p, stream in streams:
            out = [int(t) for chunk in stream for t in chunk]
            logits = reference_logits(weights, cfg, p + out[:-1], [len(p) - 1 + i for i in range(len(out))])
            assert out == logits.argmax(-1).tolist()
            logp = jax.nn.log_softmax(logits, axis=-1)[np.arange(len(out)), out]
            np.testing.assert_allclose(stream.logprobs, np.asarray(logp), atol=2e-4)
        stats = engine.stats()
    finally:
        engine.close()
    assert engine.gen.counter_names[: len(MOE_COUNTERS) + 2] == MOE_COUNTERS + WINDOW_COUNTERS
    assert stats["moe"]["local_pairs"] == stats["moe"]["routed_pairs"] > 0  # every expert held: every pair is local
    # the CPU's read gathers the rows; the sliding layers' counters count the chip's kernel read alone
    assert stats["decode_attention_path"] == "gather" and set(stats["window"]) == {*WINDOW_COUNTERS, "decode"}
    assert stats["kv_positions_needed"] > 0


# ------------------------------------------------------------------ ExpertShare's options


def share(x, first=0, count=8, *, seed=0, **options):
    """An ExpertShare of 8 experts (top-3, width 32) holding ``experts_held = (first, count)``, its weights the
    uncut layer's slice; returns ``(layer, params)``."""
    layer = ExpertShare(n_experts=8, experts_held=(0, 8), hidden_dim=32, k=3, dtype=jnp.float32, **options)
    params = layer.init(jax.random.PRNGKey(seed), x)["params"]
    experts = {name: {"kernel": params["experts"][name]["kernel"][first : first + count]} for name in ("wg", "wi", "wo")}
    held = ExpertShare(n_experts=8, experts_held=(first, count), hidden_dim=32, k=3, dtype=jnp.float32, **options)
    return held, {**params, "experts": experts}


def test_softmax_scoring_is_the_softmax_over_the_chosen_logits():
    """Softmax scores over the 8 logits, the chosen 3 renormalised, through ReGLU experts: the same as a softmax over
    the 3 largest logits, by a hand-written dense formula; the layer keeps no selection bias."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 64), jnp.float32)
    layer, params = share(x, score="softmax", activation="relu")
    out = layer.apply({"params": params}, x)
    assert "router_bias" not in params
    tokens = x.reshape(10, 64)
    logits = tokens @ params["router"]["kernel"]
    top, chosen = jax.lax.top_k(logits, 3)
    weights = jax.nn.softmax(top, axis=-1)
    wg, wi, wo = (params["experts"][name]["kernel"] for name in ("wg", "wi", "wo"))
    # [N, E, D]: every expert on every token
    every = jnp.einsum("nef,efd->ned", jax.nn.relu(jnp.einsum("nd,edf->nef", tokens, wg)) * jnp.einsum("nd,edf->nef", tokens, wi), wo)
    want = jnp.sum(jnp.take_along_axis(every, chosen[:, :, None], axis=1) * weights[:, :, None], axis=1)
    np.testing.assert_allclose(np.asarray(out).reshape(10, 64), np.asarray(want), atol=1e-5)


def test_two_shares_add_up_to_the_whole_layer_under_softmax_routing():
    """The shares (0, 4) and (4, 4) of a softmax-routed ReGLU layer, each told to route from the same router input,
    add up to the layer that holds all 8 (the routing is over all 8 on each share; what is absent is left out)."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 64), jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 12, 64), jnp.float32)
    options = dict(score="softmax", activation="relu")
    whole, params = share(x, **options)
    want = whole.apply({"params": params}, x, router_input=h)
    parts = []
    for first in (0, 4):
        layer, part = share(x, first, 4, **options)
        parts.append(layer.apply({"params": part}, x, router_input=h))
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]), np.asarray(want), atol=1e-5)
    assert float(jnp.abs(parts[0] - want).max()) > 1e-2  # a share alone is not the layer


def test_routing_follows_the_router_input():
    """Routing from ``router_input`` is routing from it: the same as ``x`` where it is ``x``, another choice where it
    differs; the experts transform ``x`` either way (for a fixed choice a ReGLU expert is homogeneous of degree two
    in its input, so doubling ``x`` and routing from the same input quadruples the output)."""
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 16, 64), jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 16, 64), jnp.float32)
    layer, params = share(x, score="softmax", activation="relu")
    apply = lambda a, r=None: layer.apply({"params": params}, a, router_input=r)  # noqa: E731
    np.testing.assert_allclose(np.asarray(apply(x, x)), np.asarray(apply(x)), atol=1e-6)
    assert float(jnp.abs(apply(x, h) - apply(x)).max()) > 1e-2
    np.testing.assert_allclose(np.asarray(apply(2.0 * x, h)), np.asarray(4.0 * apply(x, h)), rtol=1e-5, atol=1e-5)


def test_the_defaults_are_the_sigmoid_routed_swiglu_layer():
    """Left at its defaults the layer is what it was before its options: sigmoid scores chosen with the selection
    bias, SwiGLU experts, routed from ``x`` (a hand-written formula, every expert held)."""
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 6, 64), jnp.float32)
    layer, params = share(x)
    params = {**params, "router_bias": jnp.linspace(-0.1, 0.1, 8)}
    out = np.asarray(layer.apply({"params": params}, x)).reshape(6, 64)
    tokens = x.reshape(6, 64)
    scores = jax.nn.sigmoid(tokens @ params["router"]["kernel"])
    _, chosen = jax.lax.top_k(scores + params["router_bias"], 3)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    wg, wi, wo = (params["experts"][name]["kernel"] for name in ("wg", "wi", "wo"))
    every = jnp.einsum("nef,efd->ned", jax.nn.silu(jnp.einsum("nd,edf->nef", tokens, wg)) * jnp.einsum("nd,edf->nef", tokens, wi), wo)
    want = jnp.sum(jnp.take_along_axis(every, chosen[:, :, None], axis=1) * weights[:, :, None], axis=1)
    np.testing.assert_allclose(out, np.asarray(want), atol=1e-5)
    with pytest.raises(ValueError, match="activation"):
        ExpertShare(n_experts=8, experts_held=(0, 8), hidden_dim=32, activation="gelu").init(jax.random.PRNGKey(0), x)
