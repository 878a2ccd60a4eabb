"""The work one chip's share of an afmoe configuration requires, from its shapes
and the program's routing counters alone.

As ``perf/work.py`` for the dense decoder: every share of a peak or of a
roofline divides one of these numbers by a measured time, and none of them comes
from what the program executes. What the shapes cannot say — how many
token-expert pairs fell on the experts held here, how many held experts a step
touched — comes from the counters ``stats()["moe"]`` (``local_pairs``,
``experts_hit``), which count routing decisions, not work done. Matmul FLOPs are
2 per multiply-add; attention counts the QK and PV contractions over the keys a
query really sees: a sliding layer's at most ``sliding_window``.

Keys read: the published ones plus ``num_experts`` (experts held here),
``router_experts`` (the router's width), ``layer_types``, ``num_dense_layers``.
"""

from __future__ import annotations

from typing import Any, Iterable, Mapping, Tuple


def attention_params(cfg: Mapping[str, Any]) -> int:
    """q, k, v, o and the output gate (as wide as q)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q = d * cfg["num_attention_heads"] * hd
    kv = 2 * d * cfg["num_key_value_heads"] * hd
    return 3 * q + kv


def expert_params(cfg: Mapping[str, Any]) -> int:
    """One routed (or shared) expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_layer_params(cfg: Mapping[str, Any]) -> int:
    return attention_params(cfg) + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_layer_fixed_params(cfg: Mapping[str, Any]) -> int:
    """What every token of an expert layer passes through, and every chip holds alike:
    attention, the shared expert(s), the router at its full width."""
    router = cfg["hidden_size"] * cfg["router_experts"]
    return attention_params(cfg) + cfg["num_shared_experts"] * expert_params(cfg) + router


def expert_layer_params(cfg: Mapping[str, Any]) -> int:
    """One expert layer as held here: the fixed part and the held experts."""
    return expert_layer_fixed_params(cfg) + cfg["num_experts"] * expert_params(cfg)


def layer_counts(cfg: Mapping[str, Any]) -> Tuple[int, int]:
    """(dense layers, expert layers)."""
    dense = min(cfg["num_dense_layers"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def sliding_layers(cfg: Mapping[str, Any]) -> int:
    return sum(1 for kind in cfg["layer_types"] if kind == "sliding_attention")


def share_params(cfg: Mapping[str, Any]) -> int:
    """Every matrix held here: layers, the embedding slice and the untied head slice
    (norm scales and the selection bias are noise)."""
    dense, expert = layer_counts(cfg)
    head = 0 if cfg.get("tie_word_embeddings") else cfg["hidden_size"] * cfg["vocab_size"]
    return dense * dense_layer_params(cfg) + expert * expert_layer_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"] + head


def kv_bytes_per_token(cfg: Mapping[str, Any], bytes_per_value: int = 2) -> int:
    """K and V of one position over all layers (both kinds keep every position: one table, one pool a layer)."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_value * cfg["num_hidden_layers"]


def fixed_weight_bytes_per_step(cfg: Mapping[str, Any], bytes_per_param: int = 2) -> int:
    """Bytes a decode step reads whatever the routing: the dense layers, the expert layers' fixed part,
    the head slice (the embedding is a row gather)."""
    dense, expert = layer_counts(cfg)
    params = dense * dense_layer_params(cfg) + expert * expert_layer_fixed_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]
    return params * bytes_per_param


def keys_seen(cfg: Mapping[str, Any], context: float) -> float:
    """Keys one query at ``context`` visible positions (itself included) attends to, summed over the layers."""
    sliding = sliding_layers(cfg)
    return sliding * min(context, cfg["sliding_window"]) + (cfg["num_hidden_layers"] - sliding) * context


def token_fixed_flops(cfg: Mapping[str, Any], context: float, head: bool) -> float:
    """Forward FLOPs of one token outside the routed experts: every layer's fixed matrices, attention over
    the keys it sees, and with ``head`` the vocabulary slice's projection."""
    dense, expert = layer_counts(cfg)
    linear = 2.0 * (dense * dense_layer_params(cfg) + expert * expert_layer_fixed_params(cfg))
    attention = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * keys_seen(cfg, context)
    return linear + attention + (2.0 * cfg["hidden_size"] * cfg["vocab_size"] if head else 0.0)


def routed_flops(cfg: Mapping[str, Any], local_pairs: float) -> float:
    """Forward FLOPs of ``local_pairs`` token-expert pairs on held experts."""
    return 2.0 * expert_params(cfg) * local_pairs


def prefill_fixed_flops(cfg: Mapping[str, Any], prompt: int) -> float:
    """A whole prompt's FLOPs outside the routed experts: position p sees p + 1 keys (a sliding layer's
    at most its window), the head once. The sum of ``token_fixed_flops`` over the positions, in closed form."""
    if prompt <= 0:
        return 0.0
    dense, expert = layer_counts(cfg)
    linear = 2.0 * (dense * dense_layer_params(cfg) + expert * expert_layer_fixed_params(cfg)) * prompt
    window, sliding = cfg["sliding_window"], sliding_layers(cfg)
    causal = prompt * (prompt + 1) / 2.0
    windowed = causal if prompt <= window else window * (window + 1) / 2.0 + (prompt - window) * window
    keys = sliding * windowed + (cfg["num_hidden_layers"] - sliding) * causal
    return linear + 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * keys + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def decode_least_seconds(
    cfg: Mapping[str, Any], peak: Mapping[str, float], steps: int, token_contexts: Iterable[int],
    experts_hit: float, local_pairs: float,
) -> Tuple[float, str]:
    """Least time for ``steps`` decode steps that produced one token at each of ``token_contexts``: the fixed
    weights once a step, one expert's bytes for each (layer, step, held expert) that a pair fell on
    (``experts_hit``), the live KV each token reads (a sliding layer's at most its window) and the KV it
    writes, against the memory peak; the tokens' FLOPs (``local_pairs`` of them routed here) against the
    compute peak. The larger, and which."""
    contexts = list(token_contexts)
    kv_layer = kv_bytes_per_token(cfg) / cfg["num_hidden_layers"]
    bytes_moved = steps * fixed_weight_bytes_per_step(cfg) + experts_hit * expert_params(cfg) * 2
    bytes_moved += kv_layer * sum(keys_seen(cfg, c) for c in contexts) + kv_bytes_per_token(cfg) * len(contexts)
    flops = sum(token_fixed_flops(cfg, c, head=True) for c in contexts) + routed_flops(cfg, local_pairs)
    by_memory = bytes_moved / peak["hbm_bytes_per_s"]
    by_compute = flops / peak["bf16_flops_per_s"]
    return (by_memory, "memory") if by_memory >= by_compute else (by_compute, "compute")
