"""The work one chip's share of a latent-attention (MLA) routed-experts configuration requires, from its shapes
and the program's counters alone.

As ``perf/work_moe.py`` for the afmoe share: every share of a peak or of a roofline divides one of these numbers by
a measured time, and none of them comes from what the program executes. The latent attention's work is counted in
the form that needs least of it, whatever the program does: **prefill** in the expanded form (one up-projection a
position, then ``nope + rope`` and ``v`` channels a head a query-key pair, over the keys a query causally sees),
**decode** in the absorbed form (``kv_rank + rope`` and ``kv_rank`` channels a head a pair; the up-projection's two
halves applied to the query and to the output, which is the same FLOPs as one up-projection of a position) reading
each live position's latent once: ``(kv_rank + rope) x 2`` bytes a token a layer, 1,152 at the published sizes,
whatever width the pool stores. What the shapes cannot say comes from counters that count decisions, not work done:
pairs routed to held experts and held experts hit (``stats()["moe"]``), and the key positions the chunks causally
needed (``stats()["latent"]["latent_positions_needed"]``). Matmul FLOPs are 2 per multiply-add.

Keys read: the published ones plus ``n_routed_experts`` (experts held here), ``router_experts`` (the router's
width) and ``first_k_dense_replace``.
"""

from __future__ import annotations

from typing import Any, Iterable, Mapping, Tuple


def attention_params(cfg: Mapping[str, Any]) -> int:
    """W_dq, W_uq, W_dkv, W_ukv, W_o (the two low-rank norms' scales are noise)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return d * qr + qr * h * (nope + rope) + d * (kvr + rope) + kvr * h * (nope + v) + h * v * d


def expert_params(cfg: Mapping[str, Any]) -> int:
    """One routed (or shared) expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_layer_params(cfg: Mapping[str, Any]) -> int:
    return attention_params(cfg) + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_layer_fixed_params(cfg: Mapping[str, Any]) -> int:
    """What every token of an expert layer passes through, and every chip holds alike:
    attention, the shared expert(s), the router at its full width."""
    router = cfg["hidden_size"] * cfg["router_experts"]
    return attention_params(cfg) + cfg["n_shared_experts"] * expert_params(cfg) + router


def expert_layer_params(cfg: Mapping[str, Any]) -> int:
    """One expert layer as held here: the fixed part and the held experts."""
    return expert_layer_fixed_params(cfg) + cfg["n_routed_experts"] * expert_params(cfg)


def layer_counts(cfg: Mapping[str, Any]) -> Tuple[int, int]:
    """(dense layers, expert layers)."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def fixed_params(cfg: Mapping[str, Any]) -> int:
    """Every layer's matrices outside the routed experts."""
    dense, expert = layer_counts(cfg)
    return dense * dense_layer_params(cfg) + expert * expert_layer_fixed_params(cfg)


def share_params(cfg: Mapping[str, Any]) -> int:
    """Every matrix held here: layers, the embedding slice and the untied head slice."""
    head = 0 if cfg.get("tie_word_embeddings") else cfg["hidden_size"] * cfg["vocab_size"]
    held = layer_counts(cfg)[1] * cfg["n_routed_experts"] * expert_params(cfg)
    return fixed_params(cfg) + held + cfg["hidden_size"] * cfg["vocab_size"] + head


def latent_bytes_per_token_layer(cfg: Mapping[str, Any], bytes_per_value: int = 2) -> int:
    """One position's latent in one layer: the compressed key-value and the shared rotary key."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * bytes_per_value


def expanded_pair_flops(cfg: Mapping[str, Any]) -> float:
    """One query-key pair in one layer, expanded: q.k over nope + rope channels and p.v over v, every head."""
    return 2.0 * cfg["num_attention_heads"] * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def absorbed_pair_flops(cfg: Mapping[str, Any]) -> float:
    """One query-key pair in one layer, absorbed: q.k over kv_rank + rope channels and p.c over kv_rank, every head."""
    return 2.0 * cfg["num_attention_heads"] * (2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def routed_flops(cfg: Mapping[str, Any], local_pairs: float) -> float:
    """Forward FLOPs of ``local_pairs`` token-expert pairs on held experts."""
    return 2.0 * expert_params(cfg) * local_pairs


def head_flops(cfg: Mapping[str, Any]) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def decode_token_flops(cfg: Mapping[str, Any], context: float) -> float:
    """One decoded token outside the routed experts: every layer's fixed matrices (the up-projection's share of them
    is what absorbing the query and un-absorbing the output cost), the absorbed read of ``context`` positions a
    layer, and the head slice."""
    return 2.0 * fixed_params(cfg) + absorbed_pair_flops(cfg) * context * cfg["num_hidden_layers"] + head_flops(cfg)


def prefill_flops(cfg: Mapping[str, Any], tokens: float, causal_pairs: float, heads_sampled: float) -> float:
    """``tokens`` prefilled outside the routed experts: the fixed matrices (one up-projection a position among them),
    the expanded attention over ``causal_pairs`` query-key pairs (summed over the layers) and one head projection
    for each of ``heads_sampled`` first tokens."""
    return 2.0 * fixed_params(cfg) * tokens + expanded_pair_flops(cfg) * causal_pairs + head_flops(cfg) * heads_sampled


def prompt_causal_pairs(cfg: Mapping[str, Any], prompt: int) -> float:
    """Query-key pairs of a whole prompt, summed over the layers: position p sees p + 1 keys."""
    return cfg["num_hidden_layers"] * prompt * (prompt + 1) / 2.0


def chunks_causal_pairs(tokens: float, chunks: float, positions_needed: float, layers: int) -> float:
    """Query-key pairs (summed over the layers) of ``chunks`` prefill chunks of ``tokens`` tokens in all, from the
    program's count of the key positions they causally needed (``positions_needed``: each chunk's last position + 1,
    a layer). A chunk of n queries ending at key e has n e - n (n - 1) / 2 pairs; with the mean chunk's n for every
    chunk (all but a prompt's last are whole) the sum is n needed - chunks layers n (n - 1) / 2."""
    if chunks <= 0:
        return 0.0
    n = tokens / chunks
    return max(0.0, n * positions_needed - chunks * layers * n * (n - 1) / 2.0)


def fixed_weight_bytes(cfg: Mapping[str, Any], bytes_per_param: int = 2) -> int:
    """Bytes a dispatch reads whatever the routing: the layers' fixed matrices and the head slice (the embedding is a row gather)."""
    return (fixed_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]) * bytes_per_param


def _larger(bytes_moved: float, flops: float, peak: Mapping[str, float]) -> Tuple[float, str]:
    by_memory = bytes_moved / peak["hbm_bytes_per_s"]
    by_compute = flops / peak["bf16_flops_per_s"]
    return (by_memory, "memory") if by_memory >= by_compute else (by_compute, "compute")


def decode_least_seconds(
    cfg: Mapping[str, Any], peak: Mapping[str, float], steps: int, token_contexts: Iterable[int],
    experts_hit: float, local_pairs: float,
) -> Tuple[float, str]:
    """Least time for ``steps`` decode steps that produced one token at each of ``token_contexts``: the fixed weights
    and the head slice once a step, one expert's bytes for each (layer, step, held expert) that a pair fell on
    (``experts_hit``), each token's live latent once a layer (1,152 B a position) and the row it writes, against
    the memory peak; the tokens' FLOPs (``local_pairs`` of them routed here) against the compute peak. The larger,
    and which."""
    contexts = list(token_contexts)
    row = latent_bytes_per_token_layer(cfg) * cfg["num_hidden_layers"]
    bytes_moved = steps * fixed_weight_bytes(cfg) + experts_hit * expert_params(cfg) * 2 + row * (sum(contexts) + len(contexts))
    flops = sum(decode_token_flops(cfg, c) for c in contexts) + routed_flops(cfg, local_pairs)
    return _larger(bytes_moved, flops, peak)


def prefill_least_seconds(
    cfg: Mapping[str, Any], peak: Mapping[str, float], chunks: float, tokens: float, positions_needed: float,
    experts_hit: float, local_pairs: float,
) -> Tuple[float, str]:
    """Least time for ``chunks`` prefill chunks of ``tokens`` tokens in all: the fixed weights once a chunk, one
    expert's bytes for each (layer, chunk, held expert) hit, the latent rows the chunks causally needed read once
    and the chunk's own written, against the memory peak; the expanded form's FLOPs over the causal pairs alone
    against the compute peak (no head: a chunk samples nothing). The larger, and which."""
    layers = cfg["num_hidden_layers"]
    pairs = chunks_causal_pairs(tokens, chunks, positions_needed, layers)
    latent = latent_bytes_per_token_layer(cfg)
    bytes_moved = chunks * (fixed_weight_bytes(cfg) - cfg["hidden_size"] * cfg["vocab_size"] * 2)
    bytes_moved += experts_hit * expert_params(cfg) * 2 + latent * (positions_needed + tokens * layers)
    flops = prefill_flops(cfg, tokens, pairs, 0.0) + routed_flops(cfg, local_pairs)
    return _larger(bytes_moved, flops, peak)
