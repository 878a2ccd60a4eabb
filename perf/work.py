"""The work a configuration's step requires, from its shapes alone.

Every roofline share and MFU the benchmark reports divides one of these numbers
by a measured time. They are computed from the configuration file and the
window's token counts, never from what the program happens to execute, so they
read the same whatever later implements the step. Matmul FLOPs are 2 per
multiply-add; attention counts the QK and PV contractions over the tokens each
query really sees (no padding, no masked-out positions, no recomputation).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Iterable, Mapping, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown device is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in perf/peaks.json")
    return table[device_kind]


# ----------------------------------------------------------------------------- decoder


def _head_dim(cfg: Mapping[str, Any]) -> int:
    return int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])


def decoder_layer_params(cfg: Mapping[str, Any]) -> int:
    """Matrix parameters of one decoder layer (q, k, v, o, gate, up, down)."""
    d, hd = cfg["hidden_size"], _head_dim(cfg)
    q = d * cfg["num_attention_heads"] * hd
    kv = 2 * d * cfg["num_key_value_heads"] * hd
    o = cfg["num_attention_heads"] * hd * d
    mlp = 3 * d * cfg["intermediate_size"]
    return q + kv + o + mlp


def decoder_params(cfg: Mapping[str, Any]) -> int:
    """All parameters: layers (matrices + two norm scales), embedding, final norm, head."""
    d, v, layers = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    head = 0 if cfg.get("tie_word_embeddings") else d * v
    return layers * (decoder_layer_params(cfg) + 2 * d) + v * d + d + head


def decoder_weight_bytes_per_step(cfg: Mapping[str, Any], bytes_per_param: int = 2) -> int:
    """Bytes of weights one decode step must read: every layer's matrices and the
    head once (the embedding is a row gather, the norm scales are noise)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return (cfg["num_hidden_layers"] * decoder_layer_params(cfg) + d * v) * bytes_per_param


def kv_bytes_per_token(cfg: Mapping[str, Any], bytes_per_value: int = 2) -> int:
    """K and V of one position over all layers."""
    return 2 * cfg["num_key_value_heads"] * _head_dim(cfg) * bytes_per_value * cfg["num_hidden_layers"]


def decoder_token_flops(cfg: Mapping[str, Any], context: float, head: bool) -> float:
    """Forward FLOPs of one token that attends to ``context`` positions (itself
    included); ``head`` adds the vocabulary projection (decode and the last
    prompt token need it, the other prompt tokens do not)."""
    layers = cfg["num_hidden_layers"]
    linear = 2.0 * layers * decoder_layer_params(cfg)
    attention = 4.0 * layers * cfg["num_attention_heads"] * _head_dim(cfg) * context
    return linear + attention + (2.0 * cfg["hidden_size"] * cfg["vocab_size"] if head else 0.0)


def prefill_flops(cfg: Mapping[str, Any], prompt: int, cached: int = 0) -> float:
    """Forward FLOPs to prefill positions ``cached .. prompt-1`` of one prompt
    (causal: position p attends to p+1 keys), the head once for the last one."""
    n = prompt - cached
    if n <= 0:
        return 0.0
    layers = cfg["num_hidden_layers"]
    linear = 2.0 * layers * decoder_layer_params(cfg) * n
    keys_seen = (prompt * (prompt + 1) - cached * (cached + 1)) / 2.0
    attention = 4.0 * layers * cfg["num_attention_heads"] * _head_dim(cfg) * keys_seen
    return linear + attention + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_bytes(cfg: Mapping[str, Any], prompt: int, cached: int, chunk: int) -> float:
    """Least bytes to prefill ``cached .. prompt-1`` in chunks of ``chunk`` tokens:
    the layers' weights once a chunk, the KV already written read once a chunk,
    the chunk's own KV written once."""
    n = prompt - cached
    if n <= 0:
        return 0.0
    weights = cfg["num_hidden_layers"] * decoder_layer_params(cfg) * 2
    kv = kv_bytes_per_token(cfg)
    total, pos = 0.0, cached
    while pos < prompt:
        width = min(chunk, prompt - pos)
        total += weights + kv * pos + kv * width
        pos += width
    return total


def decode_least_seconds(
    cfg: Mapping[str, Any], peak: Mapping[str, float], steps: int, token_contexts: Iterable[int]
) -> Tuple[float, str]:
    """Least time for ``steps`` decode steps that produced one token at each of
    ``token_contexts`` (the positions each token attended to): weights once a
    step plus the live KV of the rows' real lengths against the memory peak, the
    tokens' FLOPs against the compute peak; the larger, and which."""
    contexts = list(token_contexts)
    kv = kv_bytes_per_token(cfg)
    bytes_moved = steps * decoder_weight_bytes_per_step(cfg) + kv * float(sum(contexts)) + kv * len(contexts)
    flops = sum(decoder_token_flops(cfg, c, head=True) for c in contexts)
    by_memory = bytes_moved / peak["hbm_bytes_per_s"]
    by_compute = flops / peak["bf16_flops_per_s"]
    return (by_memory, "memory") if by_memory >= by_compute else (by_compute, "compute")


# ----------------------------------------------------------------------------- encoder


def encoder_layer_params(cfg: Mapping[str, Any]) -> int:
    d = cfg["hidden_size"]
    return 4 * d * d + 2 * d * cfg["intermediate_size"]


def encoder_params(cfg: Mapping[str, Any], published_biases: bool = False) -> int:
    """Parameters of the encoder as the program holds it (no matrix biases; pass
    ``published_biases`` for the published count)."""
    d, ff, layers = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    embed = (cfg["vocab_size"] + cfg["max_position_embeddings"] + cfg["type_vocab_size"]) * d + 2 * d
    layer = encoder_layer_params(cfg) + 4 * d + ((4 * d + ff + d) if published_biases else 0)
    head = d * d + d + d * cfg["num_labels"] + cfg["num_labels"]
    return embed + layers * layer + head


def encoder_train_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    """Forward + backward FLOPs a trained position requires: 6 per matrix
    parameter, plus bidirectional attention over ``seq`` keys (4 forward, 8
    backward per head-dim and key). Embedding lookups and the pooled head are
    left out (a row gather; one position a row). Recomputation is not counted."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    return 6.0 * layers * encoder_layer_params(cfg) + 12.0 * layers * d * seq
