"""The glm4_moe_lite decoder and its latent attention against the plain reference
(``perf/reference/glm4_moe_lite_decoder.py``: float32 ``jax.numpy``, one sequence, the expanded form only, experts
by a Python loop), at small sizes on the CPU with seeded weights: the two reads of one latent row, the uncached
forward, prefill + decode through the contiguous and the paged latent cache, the serving engine with chunked
admission and a radix hit, the shares of a layer adding up to the uncut layer, and the engine's counters.

Tolerances: everything computes in float32 at ``highest`` matmul precision, so program and reference differ by
summation order alone: 2e-4 on logits of unit scale (the afmoe tests' bound), 1e-5 where one module is compared
with itself."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import glm4_moe_lite_decoder as reference
from perf.systems.mla_moe_serving import module_config
from unionml_tpu.models import GenerationConfig, Generator, Glm4MoeLiteTransformer
from unionml_tpu.models.glm4_moe_lite import LATENT_COUNTERS, Glm4MoeLiteBlock
from unionml_tpu.models.generate import init_cache
from unionml_tpu.models.layers import LatentAttention
from unionml_tpu.models.moe import MOE_COUNTERS
from unionml_tpu.serving import ContinuousBatcher


def config(**changes):
    """A configuration file's keys at test size: one dense layer, then three expert layers; 8 experts routed
    over, of which 4 (2..5) held, top-2."""
    cfg = dict(
        hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
        v_head_dim=16, vocab_size=256, intermediate_size=128, moe_intermediate_size=32, n_routed_experts=4,
        router_experts=8, experts_first=2, num_experts_per_tok=2, n_shared_experts=1, num_hidden_layers=4,
        first_k_dense_replace=1, rope_theta=1000000.0, rms_norm_eps=1e-5, norm_topk_prob=True, routed_scaling_factor=1.8,
        max_position_embeddings=128, precision={"compute_dtype": "float32"},
    )
    cfg.update(changes)
    return cfg


def module_for(cfg, **overrides):
    """The program's module for a configuration file's keys, by the benchmark's own mapping, in float32."""
    return Glm4MoeLiteTransformer(module_config(cfg, param_dtype=jnp.float32, max_seq_len=128, **overrides))


@pytest.fixture(scope="module")
def weights():
    return reference.make_weights(config(), 7, dtype=jnp.float32)


@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def prompt(n, seed=0):
    return np.random.default_rng(seed + n).integers(1, 256, size=n).tolist()


def reference_logits(weights, cfg, tokens, rows):
    return reference.logits_at(weights, cfg, tokens, rows, pad_to=16)


# ------------------------------------------------------------------ (a) the two reads of one latent row


def latent_layer():
    return LatentAttention(n_heads=4, q_rank=24, kv_rank=16, nope_dim=12, rope_dim=4, v_dim=16, rope_theta=1e6,
                           norm_epsilon=1e-5, dtype=jnp.float32)


def test_the_expanded_and_the_absorbed_read_agree_on_the_same_latent_row():
    """One layer, one row cache of width 128 (20 values of latent, the rest zeros): a chunk written and read back
    absorbed, then single tokens (absorbed too), equal the uncached forward over the whole sequence, which is the
    expanded read of the same latent rows."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 64), jnp.float32)
    params = latent_layer().init(jax.random.PRNGKey(1), x)["params"]
    whole, sown = latent_layer().apply({"params": params}, x, mutable=["kvs"])  # uncached: expanded, causal
    positions = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
    cache = {"k": jnp.zeros((2, 32, 1, 128), jnp.float32)}
    out, cache = latent_layer().apply({"params": params}, x[:, :16], positions, None, cache)
    np.testing.assert_allclose(np.asarray(out), np.asarray(whole[:, :16]), atol=1e-5)
    for t in range(16, 24):  # decode: one token
        out, cache = latent_layer().apply({"params": params}, x[:, t : t + 1], jnp.full((2, 1), t), None, cache)
        np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(whole[:, t]), atol=1e-5)
    # the cached rows are the latent the expanded read up-projected (sown by the uncached forward), the tail zeros
    np.testing.assert_allclose(np.asarray(cache["k"][:, :24, :, :20]), np.asarray(sown["kvs"]["k"][0]), atol=1e-6)
    assert float(jnp.abs(cache["k"][..., 20:]).max()) == 0.0 and float(jnp.abs(cache["k"][:, 24:]).max()) == 0.0


# ------------------------------------------------------------------ (b) the model against the reference


@pytest.mark.parametrize("length", [5, 16, 27])
def test_uncached_forward_equals_reference(weights, length):
    cfg, tokens = config(), prompt(length)
    logits = module_for(cfg).apply({"params": weights}, jnp.asarray(tokens)[None])
    np.testing.assert_allclose(np.asarray(logits[0]), reference_logits(weights, cfg, tokens, list(range(length))), atol=2e-4)


@pytest.mark.parametrize("chunk", [None, 8], ids=["one_dispatch", "chunked"])
def test_prefill_then_decode_through_the_contiguous_latent_cache_equals_reference(weights, chunk):
    """Generator: prompts of several lengths in one batch (right-padded, rows masked); every decoded position's
    logits against the reference's full forward. The cache is one latent plane a layer."""
    cfg, new = config(), 10
    module = module_for(cfg)
    assert set(init_cache(module.config, 1, 8)[0]) == {"k"}
    gen = Generator(module, weights, GenerationConfig(max_new_tokens=new, temperature=0.0, prompt_buckets=(32,), prefill_chunk=chunk))
    prompts = [prompt(27), prompt(3), prompt(9)]
    served = gen(prompts)
    for p, out in zip(prompts, served.tolist()):
        logits = reference_logits(weights, cfg, p + out[:-1], [len(p) - 1 + i for i in range(new)])
        assert out == logits.argmax(-1).tolist()


def served_against_reference(weights, cfg, p, stream):
    """One stream's tokens are the reference's argmax and its log-probabilities the reference's log-softmax."""
    out = [int(t) for chunk in stream for t in chunk]
    logits = reference_logits(weights, cfg, p + out[:-1], [len(p) - 1 + i for i in range(len(out))])
    assert out == logits.argmax(-1).tolist()
    logp = jax.nn.log_softmax(logits, axis=-1)[np.arange(len(out)), out]
    np.testing.assert_allclose(stream.logprobs, np.asarray(logp), atol=2e-4)
    return out


def test_engine_over_the_paged_latent_cache_equals_reference_with_a_radix_hit(weights):
    """ContinuousBatcher: chunked admission into a dense latent row, the paste into latent pages, decode through the
    block table (the gather read on the CPU), then the same document again with another question: the radix cache
    serves its pages (latent rows, gathered back into the row cache) and the suffix's chunks attend over them.
    Logits, by the served log-probabilities, against the reference's full forward."""
    cfg = config()
    gen = Generator(module_for(cfg), weights, GenerationConfig(max_new_tokens=12, temperature=0.0, prompt_buckets=(16, 32, 48)))
    engine = ContinuousBatcher(gen, slots=4, decode_chunk=4, block_size=4, admit_chunk=16, pool_blocks=64, prefix_cache=True)
    try:
        document = prompt(30, seed=5)
        asks = [document + prompt(7, seed=1), prompt(5), document + prompt(11, seed=2)]
        streams = [(p, engine.submit(p, logprobs=True)) for p in asks[:2]]
        for p, stream in streams:
            served_against_reference(weights, cfg, p, stream)
        before = engine.stats()["prefix_cache"]
        served_against_reference(weights, cfg, asks[2], engine.submit(asks[2], logprobs=True))
        stats = engine.stats()
        assert stats["prefix_cache"]["hits"] == before["hits"] + 1
        assert stats["prefix_cache"]["tokens_avoided"] - before["tokens_avoided"] == 30  # the whole document: its full blocks shared, its partial tail block copied
        assert stats["decode_attention_path"] == "latent_gather"
        width = gen.module.config.cache_layout["k"][1]
        assert stats["kv_layout"] == {
            "planes": {"k": {"heads": 1, "width": width, "value_bytes": 4}}, "block_bytes": 4 * 4 * width * 4,
        }
        assert stats["kv_blocks"]["block_bytes"] == stats["kv_layout"]["block_bytes"]
    finally:
        engine.close()


# ------------------------------------------------------------------ (c) the share


@pytest.mark.parametrize("held", [1, 2, 8])
def test_the_shares_of_a_block_add_up_to_the_uncut_reference_layer(held):
    """A whole expert block (latent attention, shared expert, routed experts) on each of the ``8 / held`` shares of
    8 experts: attention and the shared expert counted once (they are the block with its experts' output
    projections zeroed: every chip computes them alike), plus every share's routed part, is the uncut reference's
    layer. With ``held = 1`` these are the eight shares of 8 experts."""
    uncut = config(n_routed_experts=8, experts_first=0, num_hidden_layers=2)
    w = reference.make_weights(uncut, 3, dtype=jnp.float32)["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(3), (24, 64), jnp.float32)
    want, _ = reference.expert_layer(reference._attention(
        x, w, n_heads=4, kv_rank=16, nope=12, rope=4, v_dim=16, theta=1e6, eps=1e-5, block=16), w, uncut)

    def block(first, count, zeroed=False):
        experts = {name: {"kernel": w["moe"]["experts"][name]["kernel"][first : first + count]} for name in ("wg", "wi", "wo")}
        if zeroed:
            experts["wo"] = {"kernel": jnp.zeros_like(experts["wo"]["kernel"])}
        cfg = module_config(dict(uncut, n_routed_experts=count, experts_first=first), param_dtype=jnp.float32, max_seq_len=128)
        return Glm4MoeLiteBlock(cfg, 1).apply({"params": {**w, "moe": {**w["moe"], "experts": experts}}}, x[None])[0]

    alike = block(0, held, zeroed=True)  # the stream, attention and the shared expert: once
    total = alike + sum(block(first, held) - alike for first in range(0, 8, held))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)
    if held < 8:  # a share alone is not the layer
        assert float(jnp.abs(block(0, held) - want).max()) > 1e-2


# ------------------------------------------------------------------ (d) the engine's counters


@pytest.mark.parametrize("n_prompt", [21, 40])
def test_engine_counters_count_the_latent_reads(weights, n_prompt, monkeypatch):
    """``stats()["latent"]``: over one request, the decode reads covered each step's visible length a layer; a
    chunk's read walked the row cache in key blocks up to its last position, so it covered
    ``min(ceil(end / block) * block, cache_len)`` positions a layer (``block`` is ``KEY_BLOCK``, 12 here; ``end`` the
    chunk's last position + 1), of which causality needed ``end``; the routing's counters ride beside them under
    ``stats()["moe"]``, and ``"decode"`` sums the decode dispatches."""
    from unionml_tpu.ops import attention

    monkeypatch.setattr(attention, "KEY_BLOCK", 12)  # the test's rows are 57 and 73 positions: five and seven blocks
    monkeypatch.setattr(attention, "ONE_TRIP_KEYS", 12)  # or rows this short would be read whole
    cfg, chunk, new, layers = config(), 16, 9, 4  # 8 decode steps: two whole dispatches
    gen = Generator(module_for(cfg), weights, GenerationConfig(max_new_tokens=new, temperature=0.0, prompt_buckets=(16, 32, 48)))
    assert gen.counter_names == MOE_COUNTERS + LATENT_COUNTERS
    engine = ContinuousBatcher(gen, slots=4, decode_chunk=4, block_size=4, admit_chunk=chunk, pool_blocks=64)
    try:
        list(engine.submit(prompt(n_prompt, seed=11)))
        stats, cache_len = engine.stats(), engine.cache_len
    finally:
        engine.close()
    ends = [min(s + chunk, n_prompt) for s in range(0, n_prompt, chunk)]
    walked = [min(-(-end // 12) * 12, cache_len) for end in ends]
    assert cache_len > 48 and walked[0] == 24 and walked[-1] < cache_len  # no chunk walks the whole row
    read = layers * sum(n_prompt + 1 + step for step in range(new - 1))  # the token just written is visible
    assert stats["latent"] == {
        "latent_positions_read": read, "latent_positions_attended": layers * sum(walked),
        "latent_positions_needed": layers * sum(ends),
        "decode": {"latent_positions_read": read, "latent_positions_attended": 0, "latent_positions_needed": 0},
    }
    assert stats["moe"]["decode"]["routed_pairs"] == 2 * 3 * (new - 1) and stats["moe"]["routed_pairs"] == 2 * 3 * (n_prompt + new - 1)


def test_the_latent_row_is_stored_in_whole_lanes():
    assert module_config(config()).cache_layout == {"k": (1, 128)}  # 16 + 4
    assert module_config(config(kv_lora_rank=512, qk_rope_head_dim=64)).cache_layout == {"k": (1, 640)}
