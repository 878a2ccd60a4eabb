"""The work one chip's share of a window-and-global routed-experts configuration (SmallThinker) requires, from its
shapes and the program's counters alone.

As ``perf/work_moe.py``: every share of a peak or of a roofline divides one of these numbers by a measured time,
and none comes from what the program executes. Every layer is plain grouped-query attention and a routed layer
with no shared expert; a layer is windowed where ``sliding_window_layout`` says 1, global where it says 0. Keys and
values are ``2 x num_key_value_heads x head_dim x 2`` bytes a position a layer (2,048 at the published sizes).
What the shapes cannot say comes from counters that count decisions, not work done: pairs routed to held experts
and held experts hit (``stats()["moe"]``), the positions of the pages the sliding layers' decode reads touched
(``stats()["window"]["window_positions_read"]``, summed over those layers) and the key positions the chunks'
reads needed (``kv_positions_needed``, summed over every layer: a windowed layer's from its window's first). A
decode token's global layers read the whole row; its windowed layers are counted at what their reads touched, in
bytes and in FLOPs alike. Matmul FLOPs are 2 per multiply-add; attention counts the QK and PV contractions.

Keys read: the published ones plus ``num_experts`` (experts held here) and ``router_experts`` (the router's width).
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Mapping, Tuple

from perf import work_mla


def attention_params(cfg: Mapping[str, Any]) -> int:
    """W_q, W_k, W_v, W_o (no biases, no norms inside)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * cfg["num_attention_heads"] * hd + 2 * d * cfg["num_key_value_heads"] * hd


def expert_params(cfg: Mapping[str, Any]) -> int:
    """One expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def layer_fixed_params(cfg: Mapping[str, Any]) -> int:
    """What every token of a layer passes through: attention and the router at its full width."""
    return attention_params(cfg) + cfg["hidden_size"] * cfg["router_experts"]


def layer_params(cfg: Mapping[str, Any]) -> int:
    return layer_fixed_params(cfg) + cfg["num_experts"] * expert_params(cfg)


def share_params(cfg: Mapping[str, Any]) -> int:
    """Every matrix held here: the layers, the embedding and the untied head."""
    head = 0 if cfg.get("tie_word_embeddings") else cfg["hidden_size"] * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * layer_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"] + head


def kinds(cfg: Mapping[str, Any]) -> Tuple[int, int]:
    """(windowed layers, global layers)."""
    windowed = sum(int(f) for f in cfg["sliding_window_layout"])
    return windowed, cfg["num_hidden_layers"] - windowed


def kv_bytes_per_position_layer(cfg: Mapping[str, Any], bytes_per_value: int = 2) -> int:
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_value


def kv_bytes_per_position(cfg: Mapping[str, Any]) -> int:
    """One position over all layers (both kinds keep every position: one table, one pool a layer)."""
    return cfg["num_hidden_layers"] * kv_bytes_per_position_layer(cfg)


def fixed_weight_bytes(cfg: Mapping[str, Any], bytes_per_param: int = 2) -> int:
    """Bytes a decode step reads whatever the routing: every layer's attention and router, and the head (the
    embedding is a row gather)."""
    return (cfg["num_hidden_layers"] * layer_fixed_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]) * bytes_per_param


def pair_flops(cfg: Mapping[str, Any]) -> float:
    """One query-key pair in one layer: q.k and p.v over every query head."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]


def routed_flops(cfg: Mapping[str, Any], local_pairs: float) -> float:
    return 2.0 * expert_params(cfg) * local_pairs


def head_flops(cfg: Mapping[str, Any]) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def decode_positions(cfg: Mapping[str, Any], token_contexts: Iterable[int], window_positions_read: float) -> float:
    """Key positions the decode reads of those tokens cover, summed over the layers: a global layer the whole
    context, the windowed layers what their reads touched (the counter, summed over them)."""
    return kinds(cfg)[1] * float(sum(token_contexts)) + float(window_positions_read)


def decode_bytes(
    cfg: Mapping[str, Any], steps: int, token_contexts: Iterable[int], experts_hit: float, window_positions_read: float
) -> Dict[str, float]:
    """What ``steps`` decode steps that produced one token at each of ``token_contexts`` move, by kind: the fixed
    weights and the head once a step, one expert's bytes for each (layer, step, held expert) a pair fell on, and
    the keys and values each token read (:func:`decode_positions`) and wrote (a position in every layer)."""
    contexts = list(token_contexts)
    kv = kv_bytes_per_position_layer(cfg) * (decode_positions(cfg, contexts, window_positions_read) + cfg["num_hidden_layers"] * len(contexts))
    return {"weights": float(steps * fixed_weight_bytes(cfg)), "experts": experts_hit * expert_params(cfg) * 2.0, "kv": float(kv)}


def decode_flops(cfg: Mapping[str, Any], token_contexts: Iterable[int], local_pairs: float, window_positions_read: float) -> float:
    """Those tokens' FLOPs: the fixed matrices and the head a token, ``local_pairs`` routed here, and the attention
    over :func:`decode_positions`."""
    contexts = list(token_contexts)
    flops = len(contexts) * (2.0 * cfg["num_hidden_layers"] * layer_fixed_params(cfg) + head_flops(cfg))
    return flops + routed_flops(cfg, local_pairs) + pair_flops(cfg) * decode_positions(cfg, contexts, window_positions_read)


def decode_least_seconds(
    cfg: Mapping[str, Any], peak: Mapping[str, float], steps: int, token_contexts: Iterable[int],
    experts_hit: float, local_pairs: float, window_positions_read: float,
) -> Tuple[float, str]:
    """Least time for those steps: :func:`decode_bytes` against the memory peak, :func:`decode_flops` against the
    compute peak. The larger, and which."""
    contexts = list(token_contexts)
    moved = sum(decode_bytes(cfg, steps, contexts, experts_hit, window_positions_read).values())
    return work_mla._larger(moved, decode_flops(cfg, contexts, local_pairs, window_positions_read), peak)


def prompt_pairs(cfg: Mapping[str, Any], prompt: int) -> float:
    """Query-key pairs of a whole prompt, summed over the layers: position p sees p + 1 keys, a windowed layer's at
    most its window."""
    window = cfg["sliding_window_size"]
    causal = prompt * (prompt + 1) / 2.0
    windowed = causal if prompt <= window else window * (window + 1) / 2.0 + (prompt - window) * window
    n_window, n_global = kinds(cfg)
    return n_window * windowed + n_global * causal


def prefill_flops(cfg: Mapping[str, Any], prompt: int) -> float:
    """A whole prompt outside the routed experts: the fixed matrices a position, its pairs, the head once."""
    return 2.0 * cfg["num_hidden_layers"] * layer_fixed_params(cfg) * prompt + pair_flops(cfg) * prompt_pairs(cfg, prompt) + head_flops(cfg)


def prefill_least_seconds(
    cfg: Mapping[str, Any], peak: Mapping[str, float], chunks: float, tokens: float, kv_positions_needed: float,
    experts_hit: float, local_pairs: float,
) -> Tuple[float, str]:
    """Least time for ``chunks`` prefill chunks of ``tokens`` tokens in all: the fixed weights once a chunk (no
    head: a chunk samples nothing), one expert's bytes for each (layer, chunk, held expert) hit, the keys and values
    the chunks' reads needed and their own written, against the memory peak; the fixed matrices, the pairs (from
    the positions needed, as ``work_mla.chunks_causal_pairs`` counts them: a windowed layer's chunk past its window
    is counted a chunk's half-square over, at most 3 % at a chunk of 256 and a window of 4,096) and the pairs
    routed here, against the compute peak. The larger, and which."""
    layers = cfg["num_hidden_layers"]
    pairs = work_mla.chunks_causal_pairs(tokens, chunks, kv_positions_needed, layers)
    moved = chunks * layers * layer_fixed_params(cfg) * 2.0 + experts_hit * expert_params(cfg) * 2.0
    moved += kv_bytes_per_position_layer(cfg) * (kv_positions_needed + tokens * layers)
    flops = 2.0 * layers * layer_fixed_params(cfg) * tokens + pair_flops(cfg) * pairs + routed_flops(cfg, local_pairs)
    return work_mla._larger(moved, flops, peak)
