"""The afmoe decoder and its expert share against the plain reference (``perf/reference/afmoe_decoder.py``:
float32 ``jax.numpy``, one sequence, experts by a Python loop), at small sizes on the CPU with seeded weights:
the uncached forward, prefill + decode through both caches and the serving engine past a small window, the
shares of a layer adding up to the uncut layer, skewed routing, padding rows, and the engine's counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import afmoe_decoder as reference
from perf.systems.afmoe_serving import module_config
from unionml_tpu.models import AfmoeTransformer, ExpertShare, GenerationConfig, Generator
from unionml_tpu.models.moe import MOE_COUNTERS
from unionml_tpu.serving import ContinuousBatcher

SLIDING, FULL = "sliding_attention", "full_attention"
WINDOW = 8


def config(**changes):
    """A configuration file's keys at test size: one dense layer, then a period of expert layers; 8 experts
    routed over, of which 4 (2..5) held, top-2."""
    cfg = dict(
        hidden_size=64, head_dim=16, num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
        intermediate_size=128, moe_intermediate_size=32, num_experts=4, router_experts=8, experts_first=2,
        num_experts_per_tok=2, num_shared_experts=1, num_hidden_layers=5, num_dense_layers=1,
        layer_types=[SLIDING] * 4 + [FULL], sliding_window=WINDOW, rope_theta=10000.0, rms_norm_eps=1e-5,
        route_norm=True, route_scale=2.448, mup_enabled=True, score_func="sigmoid",
        max_position_embeddings=128, precision={"compute_dtype": "float32"},
    )
    cfg.update(changes)
    return cfg


def module_for(cfg):
    """The program's module for a configuration file's keys, by the benchmark's own mapping, in float32."""
    return AfmoeTransformer(module_config(cfg, param_dtype=jnp.float32, max_seq_len=128))


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


# ------------------------------------------------------------------ (a) the model against the reference


@pytest.mark.parametrize("length", [5, WINDOW, 3 * WINDOW + 3])
def test_uncached_forward_equals_reference(weights, length):
    cfg, tokens = config(), prompt(length)
    logits = module_for(cfg).apply({"params": weights}, jnp.asarray(tokens)[None])
    np.testing.assert_allclose(np.asarray(logits[0]), reference_logits(weights, cfg, tokens, list(range(length))), atol=2e-4)


@pytest.mark.parametrize("chunk", [None, 8], ids=["one_dispatch", "chunked"])
def test_prefill_then_decode_through_the_contiguous_cache_equals_reference(weights, chunk):
    """Generator: prompts of several lengths in one batch (right-padded, rows masked), contexts past the window
    on sliding and full layers alike; every decoded position's logits against the reference's full forward."""
    cfg, new = config(), 10
    gen = Generator(module_for(cfg), weights, GenerationConfig(max_new_tokens=new, temperature=0.0, prompt_buckets=(32,), prefill_chunk=chunk))
    prompts = [prompt(27), prompt(3), prompt(WINDOW + 1)]
    served = gen(prompts)
    for p, out in zip(prompts, served.tolist()):
        logits = reference_logits(weights, cfg, p + out[:-1], [len(p) - 1 + i for i in range(new)])
        assert out == logits.argmax(-1).tolist()


@pytest.mark.parametrize("lengths", [(40, 5, 23), (2 * WINDOW, WINDOW - 1)], ids=["long", "at_the_window"])
def test_engine_over_the_paged_cache_equals_reference(weights, lengths):
    """ContinuousBatcher: chunked admission into a dense row, the paste into pages, decode through the block table
    (the gather read, masked to the window): served tokens are the reference's argmax and the served
    log-probabilities its log-softmax, position by position."""
    cfg = config()
    gen = Generator(module_for(cfg), weights, GenerationConfig(max_new_tokens=12, temperature=0.0, prompt_buckets=(16, 32, 48)))
    engine = ContinuousBatcher(gen, slots=4, decode_chunk=4, block_size=4, admit_chunk=16, pool_blocks=64, prefix_cache=True)
    try:
        streams = [(p, engine.submit(p, logprobs=True)) for p in map(prompt, lengths)]
        for p, stream in streams:
            out = [int(t) for chunk in stream for t in chunk]
            logits = reference_logits(weights, cfg, p + out[:-1], [len(p) - 1 + i for i in range(len(out))])
            assert out == logits.argmax(-1).tolist()
            logp = jax.nn.log_softmax(logits, axis=-1)[np.arange(len(out)), out]
            np.testing.assert_allclose(stream.logprobs, np.asarray(logp), atol=2e-4)
        assert engine.stats()["decode_attention_path"] == "gather"
    finally:
        engine.close()


# ------------------------------------------------------------------ (b) - (d) the expert share


def layer_case(seed=3, tokens=24, bias=None):
    """One expert layer's weights (all 8 experts) and the residual stream into it."""
    cfg = config(num_experts=8, experts_first=0)
    w = reference.make_weights(cfg, seed, dtype=jnp.float32)["layer_1"]
    if bias is not None:
        w = {**w, "moe": {**w["moe"], "router_bias": jnp.asarray(bias, jnp.float32)}}
    x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, cfg["hidden_size"]), jnp.float32)
    return cfg, w, x


def routed_part(cfg, w, m, first, count, token_mask=None, counters=False):
    """ExpertShare over experts ``first .. first + count`` of the layer's 8, on normed activations ``m [N, D]``."""
    experts = {name: {"kernel": w["moe"]["experts"][name]["kernel"][first : first + count]} for name in ("wg", "wi", "wo")}
    layer = ExpertShare(n_experts=8, experts_held=(first, count), hidden_dim=cfg["moe_intermediate_size"], k=2,
                        route_scale=cfg["route_scale"], dtype=jnp.float32, param_dtype=jnp.float32)
    params = {"router": w["moe"]["router"], "router_bias": w["moe"]["router_bias"], "experts": experts}
    out, sown = layer.apply({"params": params}, m[None], token_mask, mutable=["counters"])
    counts = {name: int(value[0]) for name, value in sown["counters"].items()}
    return (out[0], counts) if counters else out[0]


def reference_parts(cfg, w, x):
    """(normed input, routed + shared sum before the closing norm) of the reference's expert layer."""
    m, chosen, weights, shared = reference._routing(x, w, eps=1e-5, top_k=2, route_norm=True, route_scale=cfg["route_scale"])
    total = shared
    for e in range(8):
        ew = w["moe"]["experts"]
        gate = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        total = total + reference._one_expert(m, ew["wg"]["kernel"][e], ew["wi"]["kernel"][e], ew["wo"]["kernel"][e], gate)
    return m, shared, total, np.asarray(chosen)


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """The routed parts of all ``8 / held`` shares plus the shared expert once are the uncut layer."""
    cfg, w, x = layer_case()
    m, shared, total, _ = reference_parts(cfg, w, x)
    parts = sum(routed_part(cfg, w, m, first, held) for first in range(0, 8, held))
    np.testing.assert_allclose(np.asarray(shared + parts), np.asarray(total), atol=2e-5)
    if held < 8:  # a share alone is not the layer
        assert float(jnp.abs(shared + routed_part(cfg, w, m, 0, held) - total).max()) > 1e-2


@pytest.mark.parametrize("favourite,first", [(3, 2), (7, 2)], ids=["held_here", "held_elsewhere"])
def test_dropless_under_skew(favourite, first):
    """A selection bias that sends every token to one expert: no capacity, nothing dropped; where that expert
    lives elsewhere this share adds only the second choices that fall on it."""
    bias = np.zeros(8, np.float32)
    bias[favourite] = 10.0
    cfg, w, x = layer_case(bias=bias)
    cfg = dict(cfg, num_experts=4, experts_first=first)
    want, chosen = reference.expert_layer(x, {**w, "moe": {**w["moe"], "experts": {
        name: {"kernel": w["moe"]["experts"][name]["kernel"][first : first + 4]} for name in ("wg", "wi", "wo")}}}, cfg)
    assert (np.asarray(chosen) == favourite).any(axis=-1).all()
    m, shared, _, _ = reference_parts(cfg, w, x)
    part, counts = routed_part(cfg, w, m, first, 4, counters=True)
    got = x + reference._rms_norm(shared + part, w["post_mlp_norm"]["scale"], 1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert counts["max_expert_load"] == (x.shape[0] if first <= favourite < first + 4 else counts["max_expert_load"])
    assert counts["routed_pairs"] == 2 * x.shape[0]


def test_padding_rows_change_no_result_and_no_counter():
    cfg, w, x = layer_case()
    m = reference_parts(cfg, w, x)[0]
    plain, counts = routed_part(cfg, w, m, 2, 4, counters=True)
    padding = jnp.full((9, m.shape[1]), 0.37, jnp.float32)  # identical rows: they would crowd one expert
    mask = jnp.asarray([True] * m.shape[0] + [False] * 9)[None]
    padded, padded_counts = routed_part(cfg, w, jnp.concatenate([m, padding]), 2, 4, token_mask=mask, counters=True)
    np.testing.assert_allclose(np.asarray(padded[: m.shape[0]]), np.asarray(plain), atol=1e-6)
    assert float(jnp.abs(padded[m.shape[0] :]).max()) == 0.0  # a masked row routes nowhere
    assert padded_counts == counts and set(counts) == set(MOE_COUNTERS)


# ------------------------------------------------------------------ (e) the engine's counters


def routing_counts(weights, cfg, sequence, n_prompt, chunk):
    """What the engine should have counted for one request, from the reference's routing over its sequence: the
    prompt in chunks of ``chunk`` tokens, then one token a decode step."""
    routing = []
    ids = np.zeros((-(-len(sequence) // 16) * 16,), np.int32)
    ids[: len(sequence)] = sequence
    reference.hidden_states(weights, cfg, ids, routing)
    first, held, k = cfg["experts_first"], cfg["num_experts"], cfg["num_experts_per_tok"]
    groups = [range(s, min(s + chunk, n_prompt)) for s in range(0, n_prompt, chunk)] + [[t] for t in range(n_prompt, len(sequence))]
    total = dict.fromkeys(MOE_COUNTERS, 0)
    decode = dict.fromkeys(MOE_COUNTERS, 0)
    for chosen in routing:
        for group in groups:
            local = [e - first for t in group for e in chosen[t] if first <= e < first + held]
            load = np.bincount(local, minlength=held)
            for into in (total, decode) if len(group) == 1 and group[0] >= n_prompt else (total,):
                into["routed_pairs"] += k * len(group)
                into["local_pairs"] += len(local)
                into["experts_hit"] += int((load > 0).sum())
                into["max_expert_load"] = max(into["max_expert_load"], int(load.max()))
    return total, decode


@pytest.mark.parametrize("n_prompt", [21, 40])
def test_engine_counters_equal_the_reference_routing(weights, n_prompt, monkeypatch):
    """``stats()["moe"]``: pairs chosen, pairs on held experts, held experts hit and the largest load, over the
    prefill chunks and the decode steps of one request (free slots ride along masked: they count nothing).
    ``kv_positions_attended`` / ``kv_positions_needed``: a chunk over positions ``start .. end - 1`` walks a layer's
    row in key blocks from the block of its first visible slot (``first = max(start - window + 1, 0)`` on a sliding
    layer, 0 on a full one) to the block of ``end - 1``: ``min(ceil(end / block) * block, cache_len) -
    first // block * block`` positions covered, ``end - first`` needed; the one-token reads count neither."""
    from unionml_tpu.ops import attention

    monkeypatch.setattr(attention, "KEY_BLOCK", 12)
    monkeypatch.setattr(attention, "ONE_TRIP_KEYS", 12)  # or rows this short would be read whole
    cfg, chunk, new = config(), 16, 9  # 8 decode steps: two whole dispatches, so no step runs past the budget
    gen = Generator(module_for(cfg), weights, GenerationConfig(max_new_tokens=new, temperature=0.0, prompt_buckets=(16, 32, 48)))
    engine = ContinuousBatcher(gen, slots=4, decode_chunk=4, block_size=4, admit_chunk=chunk, pool_blocks=64)
    try:
        p = prompt(n_prompt, seed=11)
        out = [int(t) for piece in engine.submit(p) for t in piece]
        stats, cache_len = engine.stats(), engine.cache_len
    finally:
        engine.close()
    total, decode = routing_counts(weights, cfg, p + out[:-1], n_prompt, chunk)
    assert stats["moe"] == {**total, "decode": decode}
    assert stats["decode_window_pages_skipped"] == 0  # the gather read masks the window: it skips nothing
    assert decode["routed_pairs"] == 2 * 4 * (new - 1)
    attended = needed = 0
    for start in range(0, n_prompt, chunk):
        end = min(start + chunk, n_prompt)
        for kind in cfg["layer_types"]:
            first = max(start - WINDOW + 1, 0) if kind == SLIDING else 0
            attended += min(-(-end // 12) * 12, cache_len) - first // 12 * 12
            needed += end - first
    assert cache_len > 48 and (stats["kv_positions_attended"], stats["kv_positions_needed"]) == (attended, needed)
