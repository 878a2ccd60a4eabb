"""The work one chip's share of a hybrid configuration requires — Kimi Delta Attention (KDA) layers with a
recurrent state a sequence, latent-attention (MLA) layers, a routed feed-forward — from its shapes and the
program's counters alone.

As ``perf/work_mla.py`` (whose MLA arithmetic this file calls, over the MLA layers alone): every share of a peak
or of a roofline divides one of these numbers by a measured time, and none comes from what the program executes.
A KDA layer's work is counted in the form that needs least of it, whatever the program does: **the recurrence**,
a head a token: a decay of the state (``d x d`` multiplies), two matrix-vector products (what the state holds
under the key; the output under the query) and a rank-one update, ``7 d^2`` FLOPs; and the state read and written
once a decode step a live row, ``2 x d x d x 4`` bytes a head beside the convolutions' tails — once a chunk in
prefill, which carries it through the chunk's positions on the chip. The chunk form the program runs for several
tokens costs more FLOPs a position (:func:`chunk_form_flops`, ~5.2 M a layer against 3.7 M) to make them matrix
products; that surplus is the program's, not the model's. What the shapes cannot say comes from counters that
count decisions, not work done: pairs routed to held experts and held experts hit (``stats()["moe"]``), key
positions the chunks causally needed (``stats()["latent"]``), live rows a decode step updated and live positions
the chunks ran (``stats()["state"]``: ``state_rows_updated``, ``state_positions_needed``, each summed over the KDA
layers). Matmul FLOPs are 2 per multiply-add.

Keys read: the published ones plus ``num_experts`` (experts held here), ``router_experts`` (the router's width),
``first_k_dense_replace`` and ``layer_types`` (``"kda"`` | ``"mla"`` a kept layer).
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Mapping, Tuple

from perf import work_mla


def kinds(cfg: Mapping[str, Any]) -> Tuple[int, int]:
    """(KDA layers, MLA layers)."""
    n_kda = sum(1 for kind in cfg["layer_types"] if kind == "kda")
    return n_kda, len(cfg["layer_types"]) - n_kda


def mla_view(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    """The configuration as ``perf/work_mla.py`` reads its attention: the MLA layers alone."""
    return {**cfg, "num_hidden_layers": kinds(cfg)[1]}


def kda_attention_params(cfg: Mapping[str, Any]) -> int:
    """W_q, W_k, W_v, the decay gate W_f, the output gate W_g (all D x H d), W_o, W_b (D x H), the three
    convolutions' taps, A_log, dt_bias and the head norm's scale."""
    d, width, h = cfg["hidden_size"], cfg["num_attention_heads"] * cfg["head_dim"], cfg["num_attention_heads"]
    return 6 * d * width + d * h + 3 * cfg["short_conv_kernel_size"] * width + h + width + cfg["head_dim"]


def mla_attention_params(cfg: Mapping[str, Any]) -> int:
    """W_q (full rank), W_dkv, W_ukv, W_o and the head-wise gate (the latent norm's scale is noise)."""
    d, h, kvr = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return d * h * (nope + rope) + d * (kvr + rope) + kvr * h * (nope + v) + h * v * d + d * h


def attention_params(cfg: Mapping[str, Any], kind: str) -> int:
    return kda_attention_params(cfg) if kind == "kda" else mla_attention_params(cfg)


#: one routed (or shared) expert (gate, up, down), the pairs routed here and the head slice: as the latent share's
expert_params, routed_flops, head_flops = work_mla.expert_params, work_mla.routed_flops, work_mla.head_flops


def layer_fixed_params(cfg: Mapping[str, Any], index: int) -> int:
    """Layer ``index``'s matrices outside the routed experts: its attention and the dense SwiGLU, or the shared
    expert(s) and the router at its full width."""
    attention = attention_params(cfg, cfg["layer_types"][index])
    if index < cfg["first_k_dense_replace"]:
        return attention + 3 * cfg["hidden_size"] * cfg["intermediate_size"]
    return attention + cfg["num_shared_experts"] * expert_params(cfg) + cfg["hidden_size"] * cfg["router_experts"]


def fixed_params(cfg: Mapping[str, Any]) -> int:
    return sum(layer_fixed_params(cfg, i) for i in range(len(cfg["layer_types"])))


def expert_layers(cfg: Mapping[str, Any]) -> int:
    return max(0, len(cfg["layer_types"]) - cfg["first_k_dense_replace"])


def share_params(cfg: Mapping[str, Any]) -> int:
    """Every parameter held here: layers, held experts, the embedding slice and the untied head slice."""
    head = 0 if cfg.get("tie_word_embeddings") else cfg["hidden_size"] * cfg["vocab_size"]
    held = expert_layers(cfg) * cfg["num_experts"] * expert_params(cfg)
    return fixed_params(cfg) + held + cfg["hidden_size"] * cfg["vocab_size"] + head


def state_bytes_per_layer(cfg: Mapping[str, Any]) -> int:
    """One sequence's state in one KDA layer: S, H x d x d float32, and the last ``taps - 1`` pre-convolution rows
    of q, k and v in bfloat16."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return h * hd * hd * 4 + (cfg["short_conv_kernel_size"] - 1) * 3 * h * hd * 2


def slot_state_bytes(cfg: Mapping[str, Any]) -> int:
    """One slot's recurrent state over all KDA layers, whatever its length."""
    return kinds(cfg)[0] * state_bytes_per_layer(cfg)


def step_flops(cfg: Mapping[str, Any]) -> float:
    """The recurrence, one token in one KDA layer: a head's decay (d^2), two matrix-vector products (2 x 2 d^2) and
    rank-one update (2 d^2)."""
    return 7.0 * cfg["num_attention_heads"] * cfg["head_dim"] ** 2


def chunk_form_flops(cfg: Mapping[str, Any], chunk: int = 64) -> float:
    """The chunk form, one position in one KDA layer: key-key and query-key products against the chunk's ``chunk``
    positions (2 x 2 chunk d), the unit-triangular solve of a right-hand side 2 d wide (chunk / 2 rows a position),
    the pseudo-values' product with the query-key matrix (2 chunk d) and three products with the d x d state."""
    hd = cfg["head_dim"]
    return cfg["num_attention_heads"] * (4.0 * chunk * hd + chunk * 2 * hd + 2.0 * chunk * hd + 6.0 * hd * hd)


def decode_token_flops(cfg: Mapping[str, Any], context: float) -> float:
    """One decoded token outside the routed experts: every layer's fixed matrices, the recurrence a KDA layer, the
    absorbed read of ``context`` positions an MLA layer, and the head slice."""
    n_kda, n_mla = kinds(cfg)
    attention = n_kda * step_flops(cfg) + n_mla * work_mla.absorbed_pair_flops(cfg) * context
    return 2.0 * fixed_params(cfg) + attention + head_flops(cfg)


def prefill_flops(cfg: Mapping[str, Any], tokens: float, causal_pairs: float, heads_sampled: float) -> float:
    """``tokens`` prefilled outside the routed experts: the fixed matrices, the recurrence a token a KDA layer, the
    expanded attention over ``causal_pairs`` query-key pairs (summed over the MLA layers), one head projection for
    each of ``heads_sampled`` first tokens."""
    attention = kinds(cfg)[0] * step_flops(cfg) * tokens + work_mla.expanded_pair_flops(cfg) * causal_pairs
    return 2.0 * fixed_params(cfg) * tokens + attention + head_flops(cfg) * heads_sampled


def prompt_causal_pairs(cfg: Mapping[str, Any], prompt: int) -> float:
    return work_mla.prompt_causal_pairs(mla_view(cfg), prompt)


def fixed_weight_bytes(cfg: Mapping[str, Any], bytes_per_param: int = 2) -> int:
    """Bytes a dispatch reads whatever the routing: the layers' fixed matrices and the head slice."""
    return (fixed_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]) * bytes_per_param


def decode_bytes(
    cfg: Mapping[str, Any], steps: int, token_contexts: Iterable[int], experts_hit: float, rows_updated: float
) -> Dict[str, float]:
    """What ``steps`` decode steps that produced one token at each of ``token_contexts`` move, by kind: the fixed
    weights and the head slice once a step, one expert's bytes for each (layer, step, held expert) a pair fell on,
    the recurrent state read and written once for each (KDA layer, live row) a step updated (``rows_updated``), and
    each token's live latent once an MLA layer (1,152 B a position, unpadded) beside the row it writes."""
    contexts = list(token_contexts)
    latent = work_mla.latent_bytes_per_token_layer(cfg) * kinds(cfg)[1]
    return {
        "weights": float(steps * fixed_weight_bytes(cfg)), "experts": experts_hit * expert_params(cfg) * 2.0,
        "state": 2.0 * state_bytes_per_layer(cfg) * rows_updated, "latent": float(latent * (sum(contexts) + len(contexts))),
    }


def decode_least_seconds(
    cfg: Mapping[str, Any], peak: Mapping[str, float], steps: int, token_contexts: Iterable[int],
    experts_hit: float, local_pairs: float, rows_updated: float,
) -> Tuple[float, str]:
    """Least time for those steps: :func:`decode_bytes` against the memory peak, the tokens' FLOPs (``local_pairs``
    of them routed here) against the compute peak. The larger, and which."""
    contexts = list(token_contexts)
    moved = sum(decode_bytes(cfg, steps, contexts, experts_hit, rows_updated).values())
    flops = sum(decode_token_flops(cfg, c) for c in contexts) + routed_flops(cfg, local_pairs)
    return work_mla._larger(moved, flops, peak)


def prefill_least_seconds(
    cfg: Mapping[str, Any], peak: Mapping[str, float], chunks: float, tokens: float, latent_positions_needed: float,
    experts_hit: float, local_pairs: float, state_positions_needed: float,
) -> Tuple[float, str]:
    """Least time for ``chunks`` prefill chunks of ``tokens`` tokens in all: the fixed weights (no head: a chunk
    samples nothing) and a row's recurrent state in and out once a chunk, one expert's bytes for each (layer,
    chunk, held expert) hit, the latent rows the chunks causally needed and their own, against the memory peak; the
    fixed matrices, the recurrence over the live positions the chunks ran (``state_positions_needed``, summed over
    the KDA layers) and the expanded attention over the causal pairs, against the compute peak. The larger, and which."""
    n_kda, n_mla = kinds(cfg)
    pairs = work_mla.chunks_causal_pairs(tokens, chunks, latent_positions_needed, n_mla)
    moved = chunks * (fixed_weight_bytes(cfg) - cfg["hidden_size"] * cfg["vocab_size"] * 2 + 2 * slot_state_bytes(cfg))
    moved += experts_hit * expert_params(cfg) * 2
    moved += work_mla.latent_bytes_per_token_layer(cfg) * (latent_positions_needed + tokens * n_mla)
    flops = 2.0 * fixed_params(cfg) * tokens + step_flops(cfg) * state_positions_needed
    flops += work_mla.expanded_pair_flops(cfg) * pairs + routed_flops(cfg, local_pairs)
    return work_mla._larger(moved, flops, peak)
