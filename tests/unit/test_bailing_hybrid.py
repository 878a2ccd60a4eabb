"""The bailing_hybrid decoder (Ling-3.0-flash's block: KDA layers beside MLA layers, group-limited routing) against
the plain reference (``perf/reference/bailing_hybrid_decoder.py``: float32 ``jax.numpy``, one sequence, KDA as the
per-token recurrence in a scan, MLA expanded, experts by a Python loop), at small sizes on the CPU with seeded
weights: the uncached forward, prefill in chunks of unequal fill then decode through the dense row and through the
engine's pool (latent pages beside a row of state a slot), the shares of a layer adding up to the uncut layer, the
engine's care of the slot state (two rows of different lengths, a slot's next tenant, preempt and resume), the
counters and the three refusals.

Tolerances: everything computes in float32 at ``highest`` matmul precision, so program and reference differ by
summation order and by the chunk form's triangular solve against the recurrence: 3e-4 on logits of unit scale (the
GLM tests' 2e-4 and the chunk form's 1e-4 at the decay's bound, ``tests/unit/test_delta_rule.py``), 1e-5 where one
module is compared with itself."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import bailing_hybrid_decoder as reference
from perf.systems.hybrid_state_serving import module_config
from unionml_tpu.models import BailingHybridTransformer, DraftSpec, GenerationConfig, Generator, SpeculativeGenerator
from unionml_tpu.models.bailing_hybrid import STATE_COUNTERS, BailingHybridBlock
from unionml_tpu.models.generate import cache_layouts, init_cache, init_paged_cache
from unionml_tpu.models.glm4_moe_lite import LATENT_COUNTERS
from unionml_tpu.models.layers import SlotPlane
from unionml_tpu.models.moe import MOE_COUNTERS
from unionml_tpu.serving import ContinuousBatcher

KINDS = ["kda", "kda", "mla", "kda"]


def config(**changes):
    """A configuration file's keys at test size: one dense KDA layer, then KDA, MLA, KDA expert layers; 16 experts
    routed over in 4 groups of which 2 stay, top-2, of which group 1 (experts 4..7) is held."""
    cfg = dict(
        hidden_size=64, num_attention_heads=4, head_dim=16, short_conv_kernel_size=4, kda_lower_bound=-5, layer_group_size=3,
        kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, vocab_size=256, intermediate_size=128,
        moe_intermediate_size=32, num_experts=4, router_experts=16, experts_first=4, n_group=4, topk_group=2,
        num_experts_per_tok=2, num_shared_experts=1, num_hidden_layers=4, layer_types=KINDS, first_k_dense_replace=1,
        rope_theta=6000000.0, rms_norm_eps=1e-6, norm_topk_prob=True, routed_scaling_factor=2.5,
        max_position_embeddings=128, precision={"compute_dtype": "float32", "state_dtype": "float32"},
    )
    cfg.update(changes)
    return cfg


def module_for(cfg, **overrides):
    """The program's module for a configuration file's keys, by the benchmark's own mapping, in float32."""
    return BailingHybridTransformer(module_config(cfg, param_dtype=jnp.float32, max_seq_len=128, **overrides))


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


# ------------------------------------------------------------------ (a) the layout: two kinds of state


def test_the_configuration_states_a_layout_a_layer():
    module = module_for(config())
    layouts = cache_layouts(module.config)
    assert [sorted(layout) for layout in layouts] == [["S", "conv"], ["S", "conv"], ["k"], ["S", "conv"]]
    assert layouts[0]["S"] == SlotPlane((4, 16, 16), jnp.dtype("float32"), 0) and layouts[0]["conv"].shape == (3, 3, 64)
    assert layouts[2]["k"][:2] == (1, 128)  # 16 + 4 in whole lanes
    row = init_cache(module.config, 2, 40)
    assert row[0]["S"].shape == (2, 4, 16, 16) and row[0]["conv"].shape == (2, 3, 3, 64) and row[2]["k"].shape == (2, 40, 1, 128)
    pool = init_paged_cache(module.config, 3, 9, 4, 10, fill_block=8)
    assert set(pool[0]) == {"S", "conv"} and pool[0]["S"].shape == (3, 4, 16, 16)  # a row a slot, no table
    assert set(pool[2]) == {"k", "table"} and pool[2]["k"].shape == (1, 9, 4, 128)


# ------------------------------------------------------------------ (b) the model against the reference


@pytest.mark.parametrize("length", [5, 16, 70])
def test_uncached_forward_equals_reference(weights, length):
    cfg, tokens = config(), prompt(length)
    logits = module_for(cfg).apply({"params": weights}, jnp.asarray(tokens)[None])
    np.testing.assert_allclose(np.asarray(logits[0]), reference_logits(weights, cfg, tokens, list(range(length))), atol=3e-4)


@pytest.mark.parametrize("chunk", [None, 8], ids=["one_dispatch", "chunked"])
def test_prefill_then_decode_through_the_dense_row_equals_reference(weights, chunk):
    """Generator: prompts of several lengths in one batch (right-padded: the chunks' fill is unequal, and a padded
    position must leave the state and the tails untouched); every decoded position's logits against the
    reference's full forward."""
    cfg, new = config(), 10
    gen = Generator(module_for(cfg), weights, GenerationConfig(max_new_tokens=new, temperature=0.0, prompt_buckets=(32,), prefill_chunk=chunk))
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
    np.testing.assert_allclose(stream.logprobs, np.asarray(logp), atol=3e-4)
    return out


def engine_for(weights, cfg=None, new=12, **options):
    gen = Generator(module_for(cfg or config()), weights, GenerationConfig(max_new_tokens=new, temperature=0.0, prompt_buckets=(16, 32, 48)))
    return ContinuousBatcher(gen, **{**dict(slots=2, decode_chunk=4, block_size=4, admit_chunk=16, pool_blocks=64), **options})


def test_engine_over_slot_state_and_latent_pages_equals_reference(weights):
    """ContinuousBatcher: chunked admission into a row cache (a zero state, zero tails, a latent row), the paste
    (latent pages by table, the state planes at row ``slot``), decode through the pool with two rows of different
    lengths live at once, and a third request that takes over a finished row's slot: each stream's logits, by the
    served log-probabilities, against the reference's full forward — so neither row disturbs the other, and a
    slot's next tenant starts from a zero state (nothing of the last tenant's is left)."""
    cfg = config()
    engine = engine_for(weights, cfg)
    try:
        asks = [prompt(37, seed=5), prompt(5), prompt(21, seed=2)]
        streams = [(p, engine.submit(p, logprobs=True)) for p in asks]  # two slots: the third waits for one
        for p, stream in streams:
            served_against_reference(weights, cfg, p, stream)
        stats = engine.stats()
        assert stats["decode_attention_path"] == "latent_gather"
        assert stats["kv_layout"]["planes"] == {"k": {"heads": 1, "width": 128, "value_bytes": 4}}
        assert stats["kv_layout"]["slot_planes"] == {"S": {"shape": [4, 16, 16], "value_bytes": 4}, "conv": {"shape": [3, 3, 64], "value_bytes": 4}}
        # only the MLA layer holds pool blocks; three KDA layers hold a row a slot
        assert stats["kv_blocks"]["block_bytes"] == 1 * 4 * 128 * 4 and stats["kv_layout"]["slot_bytes"] == 3 * (4 * 16 * 16 + 3 * 3 * 64) * 4
        assert stats["state"]["slot_bytes"] == stats["kv_layout"]["slot_bytes"] and stats["state"]["state_bytes_live"] == 0
    finally:
        engine.close()


def test_two_slots_of_different_lengths_decode_as_each_alone(weights):
    """The same two prompts served together and each alone (a one-slot engine): equal log-probabilities, 1e-5."""
    asks = [prompt(33, seed=1), prompt(6, seed=3)]

    def serve(engine, batch):
        streams = [engine.submit(p, logprobs=True) for p in batch]
        return [([int(t) for c in s for t in c], list(s.logprobs)) for s in streams]

    together = engine_for(weights)
    try:
        both = serve(together, asks)
    finally:
        together.close()
    for p, (tokens, logps) in zip(asks, both):
        alone = engine_for(weights, slots=1)
        try:
            (solo_tokens, solo_logps), = serve(alone, [p])
        finally:
            alone.close()
        assert tokens == solo_tokens
        np.testing.assert_allclose(logps, solo_logps, atol=1e-5)


def test_preempt_and_resume_reproduce_the_uninterrupted_tokens(weights):
    """A pool too small for both rows' whole lives: the engine preempts one (recompute preemption: it comes back as
    prompt + echo, which rebuilds the recurrent state from a zero one) and every stream still equals the
    reference's greedy continuation."""
    cfg = config()
    engine = engine_for(weights, cfg, new=24, pool_blocks=20)  # cache_len 76 -> 19 blocks a worst-case row
    try:
        asks = [prompt(30, seed=8), prompt(28, seed=9)]
        streams = [(p, engine.submit(p, logprobs=True)) for p in asks]
        for p, stream in streams:
            served_against_reference(weights, cfg, p, stream)
        assert engine.stats()["kv_blocks"]["preemptions"] >= 1
    finally:
        engine.close()


def test_handoff_carries_the_slot_planes_beside_the_pages(weights):
    """A prefill-role engine exports a prompt's latent pages and its state planes; a decode-role engine imports
    both and decodes to the reference's tokens."""
    cfg, p = config(), prompt(19, seed=4)
    exporter, importer = engine_for(weights, cfg, role="prefill"), engine_for(weights, cfg, role="decode")
    try:
        stream = exporter.submit(p, export_handoff=True)
        first = [int(t) for c in stream for t in c]
        payload = stream.handoff
        assert set(payload["pages"][0]) == {"S", "conv"} and payload["pages"][0]["S"].shape == (1, 4, 16, 16)
        assert payload["pages"][2]["k"].shape == (1, 5, 4, 128)  # 19 positions: five pages of four
        rest = [int(t) for c in importer.import_handoff(payload) for t in c]
        out = first + rest
        logits = reference_logits(weights, cfg, p + out[:-1], [len(p) - 1 + i for i in range(len(out))])
        assert len(out) == 12 and out == logits.argmax(-1).tolist()
    finally:
        exporter.close()
        importer.close()


# ------------------------------------------------------------------ (c) the share


@pytest.mark.parametrize("kind", ["kda", "mla"])
def test_the_eight_groups_shares_add_up_to_the_uncut_reference_layer(kind):
    """A whole expert block on each of the 8 shares of 32 experts in 8 routing groups (one group a share): the
    mixer and the shared expert counted once (they are the block with its experts' output projections zeroed:
    every chip computes them alike), plus every share's routed part, is the uncut reference's layer."""
    uncut = config(num_experts=32, router_experts=32, experts_first=0, n_group=8, topk_group=4, num_experts_per_tok=4,
                   num_hidden_layers=2, layer_types=["kda", kind])
    w = reference.make_weights(uncut, 3, dtype=jnp.float32)["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(3), (24, 64), jnp.float32)
    if kind == "kda":
        mixed = reference._kda(x, w, n_heads=4, head_dim=16, bound=-5.0, eps=1e-6)
    else:
        mixed = reference._mla(x, w, n_heads=4, kv_rank=16, nope=12, rope=4, v_dim=16, theta=6e6, eps=1e-6, block=16)
    want, chosen = reference.expert_layer(mixed, w, uncut)
    assert all(len({int(e) // 4 for e in row}) <= 4 for row in np.asarray(chosen))  # at most topk_group groups a token

    def block(first, count, zeroed=False):
        experts = {name: {"kernel": w["moe"]["experts"][name]["kernel"][first : first + count]} for name in ("wg", "wi", "wo")}
        if zeroed:
            experts["wo"] = {"kernel": jnp.zeros_like(experts["wo"]["kernel"])}
        cfg = module_config(dict(uncut, num_experts=count, experts_first=first), param_dtype=jnp.float32, max_seq_len=128)
        return BailingHybridBlock(cfg, 1).apply({"params": {**w, "moe": {**w["moe"], "experts": experts}}}, x[None])[0]

    alike = block(0, 4, zeroed=True)
    total = alike + sum(block(first, 4) - alike for first in range(0, 32, 4))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)
    assert float(jnp.abs(block(0, 4) - want).max()) > 1e-2  # a share alone is not the layer


# ------------------------------------------------------------------ (d) counters and refusals


def test_engine_counters_count_the_state_updates(weights):
    """``stats()["state"]``: over one request of 21 tokens and 8 decode steps, every KDA layer (3) updated the live
    row once a step, and the chunk form ran two chunks of 16 positions of which 21 were the prompt's."""
    engine = engine_for(weights, new=9, slots=4)
    assert engine.gen.counter_names == MOE_COUNTERS + LATENT_COUNTERS + STATE_COUNTERS
    try:
        list(engine.submit(prompt(21, seed=11)))
        stats = engine.stats()
    finally:
        engine.close()
    assert {k: stats["state"][k] for k in STATE_COUNTERS} == {
        "state_rows_updated": 3 * 8, "state_positions_run": 3 * 2 * 16, "state_positions_needed": 3 * 21,
    }
    assert stats["state"]["decode"] == {"state_rows_updated": 24, "state_positions_run": 0, "state_positions_needed": 0}
    assert stats["latent"]["latent_positions_needed"] == 16 + 21  # the one MLA layer


def test_an_engine_over_slot_state_refuses_what_would_resume_mid_sequence(weights, monkeypatch):
    """Each refusal a ``ValueError`` at construction that names the mechanism: the radix prefix cache (asked for,
    or switched on by the serve CLI's environment default), speculative decoding, int8 pages."""
    module = module_for(config())
    plain = GenerationConfig(max_new_tokens=4, temperature=0.0, prompt_buckets=(16,))
    with pytest.raises(ValueError, match="prefix_cache over a model that keeps a recurrent state"):
        ContinuousBatcher(Generator(module, weights, plain), slots=2, prefix_cache=True)
    monkeypatch.setenv("UNIONML_TPU_PREFIX_CACHE", "1")
    with pytest.raises(ValueError, match="UNIONML_TPU_PREFIX_CACHE switched it on"):
        ContinuousBatcher(Generator(module, weights, plain), slots=2)
    monkeypatch.delenv("UNIONML_TPU_PREFIX_CACHE")
    draft = DraftSpec(module=module, params=weights, gamma=2)
    with pytest.raises(ValueError, match="roll the state back"):  # no engine needed: the generator's own draft path
        Generator(module, weights, GenerationConfig(max_new_tokens=4, temperature=0.0, draft=draft))
    with pytest.raises(ValueError, match="roll the state back"):
        SpeculativeGenerator(module, weights, module, weights, plain, gamma=2)
    with pytest.raises(ValueError, match="int8.*stated cache layout"):
        ContinuousBatcher(Generator(module, weights, GenerationConfig(max_new_tokens=4, temperature=0.0, kv_cache_dtype="int8")), slots=2)
    with pytest.raises(ValueError, match="no position axis to cut"):
        Generator(module, weights, plain).cache_prefix([1, 2, 3])
    ContinuousBatcher(Generator(module, weights, plain), slots=2, prefix_cache=False).close()  # the stated default serves


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 emulated devices")
def test_under_a_mesh_the_state_and_the_tails_shard_their_heads():
    """``bailing_hybrid_partition_rules`` on a ``model=2`` mesh of virtual CPU devices: a KDA layer's ``q_proj``,
    ``f_proj``, ``g_proj`` shard by columns, ``o_proj`` by rows and the taps by channel; the state ``S`` shards its
    heads and the tails their channels, in the pool as in a row cache; the MLA layer's latent plane replicates;
    generation through the sharded program emits the unsharded run's tokens. Placed here, measured by no cell."""
    from unionml_tpu.models import BailingHybridConfig, bailing_hybrid_partition_rules
    from unionml_tpu.parallel import MeshSpec

    cfg = BailingHybridConfig.tiny(dtype=jnp.float32)
    module = BailingHybridTransformer(cfg)
    params = module.init(jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))["params"]
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(16,))
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5, 8, 9]]
    expected = Generator(module, params, gen_cfg)(prompts)
    mesh = MeshSpec(model=2).build(jax.devices()[:2])
    gen = Generator(module, params, gen_cfg, mesh=mesh, partition_rules=bailing_hybrid_partition_rules())
    spec = lambda leaf: tuple(leaf.sharding.spec)  # noqa: E731
    kda, mla = gen.params["layer_0"]["attn"], gen.params["layer_2"]["attn"]
    for name in ("q_proj", "k_proj", "v_proj", "f_proj", "g_proj"):
        assert spec(kda[name]["kernel"])[-1] == "model", name
    assert spec(kda["o_proj"]["kernel"])[0] == "model" and spec(kda["conv_taps"])[-1] == "model" and spec(kda["dt_bias"]) == ("model",)
    assert "model" not in spec(kda["b_proj"]["kernel"]) and kda["A_log"].sharding.is_fully_replicated
    assert spec(mla["q_proj"]["kernel"])[-1] == "model" and "model" not in spec(mla["kv_down"]["kernel"])
    pool = gen._place_paged_cache(init_paged_cache(cfg, 2, 5, 4, 6, fill_block=4))
    assert spec(pool[0]["S"])[:2] == (None, "model") and spec(pool[0]["conv"]) == (None, None, None, "model")
    assert pool[2]["k"].sharding.is_fully_replicated and pool[2]["table"].sharding.is_fully_replicated
    row = gen._place_cache(init_cache(cfg, 2, 16))
    assert spec(row[0]["S"])[1] == "model" and spec(row[0]["conv"])[-1] == "model"
    np.testing.assert_array_equal(np.asarray(gen(prompts)), np.asarray(expected))
