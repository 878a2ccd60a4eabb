"""Plain reference for the SmallThinker decoder (SmallThinker-21BA3B): window and position-free global attention
layers, every layer a softmax-routed layer of ReGLU experts whose router reads the block's input.

Straight ``jax.numpy`` in float32 with matmuls at ``highest`` precision: no cache, no batching, no kernels, no
sort, nothing imported from the program. One sequence at a time, one layer at a time (each layer's bfloat16
weights are upcast on the way in), every held expert by a Python loop over them, applied densely to every position
under its routing weight (zero where it was not chosen). The window is a mask. Attention runs a block of queries
at a time over the keys that block can see (from the window's first, or from 0, to the block's last), so that a
sequence of 16,384 positions fits beside the weights. The norms, the rotary, the int8 rounding and the head are
``perf/reference/glm4_moe_lite_decoder.py``'s own functions. Equations, with ``h`` the stream, ``R`` an RMS norm
with a learned scale (eps the file's ``rms_norm_eps``)::

    h0 = E[tokens]
    r = h W_r                     (router_experts logits, float32; the block's INPUT, before attention)
    p = softmax(r);  sel = top-k of p;  w = p[sel] / (sum p[sel] + 1e-20)     (norm_topk_prob; no bias, no scale)
    a = R(h);  q = W_q a;  k = W_k a;  v = W_v a      (H query heads, KV key/value heads of head_dim)
    layer i with sliding_window_layout[i] = 1: key j visible to query t iff t - window < j <= t
                                         = 0: key j visible to query t iff j <= t
    layer i with rope_layout[i] = 1: rotary (rope_theta) on q and k; = 0: none
    h <- h + W_o softmax(q k^T / sqrt(head_dim)) v
    m = R(h)
    h <- h + sum over e in sel, e held here, of w_e (relu(m W_g^e) * (m W_u^e)) W_d^e
    logits = W_head R(h)

**The share**: ``num_experts`` counts the experts held here, ``experts_first`` says where they start among the
router's ``router_experts`` outputs; the routing is over all of them and an absent expert's part is left out (in
the benchmark's configuration every expert is held). The tree's layout is the interface program and reference
agree on::

    embed/embedding [V, D]; final_norm/scale [D]; lm_head/kernel [D, V]; layer_i/{attn_norm,mlp_norm}/scale [D]
    layer_i/attn/q_proj/kernel [D, H*hd]; {k_proj,v_proj}/kernel [D, KV*hd]; o_proj/kernel [H*hd, D]
    layer_i/moe/router/kernel [D, router_experts]; layer_i/moe/experts/{wg,wi}/kernel [held, D, F], wo [held, F, D]

``int8_weights=True`` is the lower-precision control: every matrix named ``kernel`` but the router's rounded to
symmetric int8 with one scale an output channel (an expert's own); norms and the embedding are left.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference.glm4_moe_lite_decoder import _head, _matrix, _rms_norm, _rope


def shapes(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    d, hd, v = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    h, kv, f = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["moe_ffn_hidden_size"]
    held, routed = cfg["num_experts"], cfg["router_experts"]
    tree: Dict[str, Any] = {"embed": {"embedding": (v, d)}, "final_norm": {"scale": (d,)}, "lm_head": {"kernel": (d, v)}}
    for i in range(cfg["num_hidden_layers"]):
        tree[f"layer_{i}"] = {
            "attn_norm": {"scale": (d,)}, "mlp_norm": {"scale": (d,)},
            "attn": {
                "q_proj": {"kernel": (d, h * hd)}, "k_proj": {"kernel": (d, kv * hd)}, "v_proj": {"kernel": (d, kv * hd)},
                "o_proj": {"kernel": (h * hd, d)},
            },
            "moe": {
                "router": {"kernel": (d, routed)},
                "experts": {"wg": {"kernel": (held, d, f)}, "wi": {"kernel": (held, d, f)}, "wo": {"kernel": (held, f, d)}},
            },
        }
    return tree


def make_weights(cfg: Mapping[str, Any], seed: int, dtype: Any = jnp.bfloat16) -> Dict[str, Any]:
    """Seeded random weights, made on the device in one jitted call, in the type they are served in. Matrices
    normal(0, 1/sqrt(fan_in)) (an expert's fan-in its own); the embedding normal(0, 1) (no multiplier: the stream
    the layers add to, and the router reads, has unit scale); norm scales ones (float32, as the program keeps them)."""
    tree = shapes(cfg)
    is_shape = lambda x: isinstance(x, tuple)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_shape)
    names = ["/".join(str(k.key) for k in path) for path, _ in flat]

    @jax.jit
    def build(key):
        out = []
        for i, (name, (_, shape)) in enumerate(zip(names, flat)):
            sub = jax.random.fold_in(key, i)
            if len(shape) == 1:
                out.append(jnp.ones(shape, jnp.float32))
            else:
                std = 1.0 if name.startswith("embed") else shape[-2] ** -0.5
                out.append((jax.random.normal(sub, shape, jnp.float32) * std).astype(dtype))
        return out

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.tree_util.tree_unflatten(treedef, build(key))


def route(h, router, *, top_k, normalize):
    """``(chosen [L, k], weights [L, k])``: a softmax over all of the router's logits, the k largest."""
    probs = jax.nn.softmax(h @ router.astype(jnp.float32), axis=-1)
    weights, chosen = jax.lax.top_k(probs, top_k)
    if normalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "theta", "eps", "window", "rope", "block", "int8"))
def _attention(x, w, *, n_heads, n_kv, head_dim, theta, eps, window, rope, block, int8=False):
    """The attention half of a block on one sequence: x [L, D] float32. ``window`` None: global; ``rope`` False:
    no rotary."""
    with jax.default_matmul_precision("highest"):
        mat = lambda name: _matrix(w["attn"][name]["kernel"], int8)  # noqa: E731
        length = x.shape[0]
        positions = jnp.arange(length)
        a = _rms_norm(x, w["attn_norm"]["scale"], eps)
        q = (a @ mat("q_proj")).reshape(length, n_heads, head_dim)
        k = (a @ mat("k_proj")).reshape(length, n_kv, head_dim)
        v = (a @ mat("v_proj")).reshape(length, n_kv, head_dim)
        if rope:
            q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        q = q.reshape(length, n_kv, n_heads // n_kv, head_dim)  # query head h reads key/value head h // group
        outs = []
        for start in range(0, length, block):  # a block of queries over the keys it can see
            end = min(start + block, length)
            first = 0 if window is None else max(0, start - window + 1)
            scores = jnp.einsum("qkgd,skd->kgqs", q[start:end], k[first:end]) * head_dim**-0.5
            rows, keys = positions[start:end, None], positions[None, first:end]
            visible = keys <= rows
            if window is not None:
                visible = visible & (keys > rows - window)
            scores = jnp.where(visible[None, None], scores, -jnp.inf)
            outs.append(jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, axis=-1), v[first:end]))
        out = jnp.concatenate(outs, axis=0).reshape(length, n_heads * head_dim)
        return x + out @ mat("o_proj")


@partial(jax.jit, static_argnames=("eps", "top_k", "normalize"))
def _routing(x, h_in, w, *, eps, top_k, normalize):
    with jax.default_matmul_precision("highest"):
        chosen, weights = route(h_in, w["moe"]["router"]["kernel"], top_k=top_k, normalize=normalize)
        return _rms_norm(x, w["mlp_norm"]["scale"], eps), chosen, weights


@partial(jax.jit, static_argnames=("int8",))
def _one_expert(m, wg, wi, wo, weight, int8=False):
    """``weight [L]`` (zero where the token did not choose this expert) times the expert's ReGLU."""
    with jax.default_matmul_precision("highest"):
        return weight[:, None] * ((jax.nn.relu(m @ _matrix(wg, int8)) * (m @ _matrix(wi, int8))) @ _matrix(wo, int8))


def expert_layer(x, h_in, w, cfg: Mapping[str, Any], int8: bool = False):
    """The feed-forward half of a block on one sequence, routed from ``h_in`` (the block's input), the held experts
    one at a time. Returns ``(h, chosen [L, k])``."""
    m, chosen, weights = _routing(
        x, h_in, w, eps=float(cfg["rms_norm_eps"]), top_k=int(cfg["moe_num_active_primary_experts"]),
        normalize=bool(cfg["norm_topk_prob"]),
    )
    first = int(cfg.get("experts_first", 0))
    experts = w["moe"]["experts"]
    for local in range(int(cfg["num_experts"])):
        weight = jnp.sum(jnp.where(chosen == first + local, weights, 0.0), axis=-1)
        x = x + _one_expert(m, experts["wg"]["kernel"][local], experts["wi"]["kernel"][local],
                            experts["wo"]["kernel"][local], weight, int8=int8)
    return x, chosen


def hidden_states(weights: Mapping[str, Any], cfg: Mapping[str, Any], ids: np.ndarray, routing: Any = None,
                  int8_weights: bool = False):
    """The residual stream after the last layer, ``[len(ids), D]`` float32. ``routing``, a list, receives each
    layer's choice ``[L, k]``."""
    x = jnp.take(weights["embed"]["embedding"], jnp.asarray(ids), axis=0).astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        w = weights[f"layer_{i}"]
        h_in = x
        x = _attention(
            x, w, n_heads=cfg["num_attention_heads"], n_kv=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
            window=int(cfg["sliding_window_size"]) if cfg["sliding_window_layout"][i] else None,
            rope=bool(cfg["rope_layout"][i]), block=512, int8=int8_weights,
        )
        x, chosen = expert_layer(x, h_in, w, cfg, int8_weights)
        if routing is not None:
            routing.append(np.asarray(chosen))
    return x


def logits_at(weights: Mapping[str, Any], cfg: Mapping[str, Any], tokens: Sequence[int], rows: Sequence[int],
              pad_to: int = 512, int8_weights: bool = False) -> np.ndarray:
    """Logits ``[len(rows), vocab]`` (float32, on the host) of one full forward pass over ``tokens`` at sequence
    positions ``rows``. The sequence is padded on the right to a multiple of ``pad_to`` (causal attention never sees
    the padding, and one token's routing never depends on another's), so few shapes compile."""
    n = len(tokens)
    width = -(-n // pad_to) * pad_to
    ids = np.zeros((width,), np.int32)
    ids[:n] = np.asarray(tokens, np.int32)
    x = hidden_states(weights, cfg, ids, int8_weights=int8_weights)
    row_ids = np.zeros((-(-len(rows) // 64) * 64,), np.int32)
    row_ids[: len(rows)] = np.asarray(rows, np.int32)
    out = _head(x, jnp.asarray(row_ids), weights["final_norm"]["scale"], weights["lm_head"]["kernel"],
                eps=float(cfg["rms_norm_eps"]), int8=int8_weights)
    return np.asarray(out)[: len(rows)]
