"""Plain reference for the glm4_moe_lite decoder (GLM-4.7-Flash; the DeepSeek-V2/V3 block), given one chip's share.

Straight ``jax.numpy`` in float32 with matmuls at ``highest`` precision: no cache,
no batching, no kernels, no sort, nothing imported from the program. One
sequence at a time, one layer at a time (each layer's bfloat16 weights are upcast
on the way in), the held experts by a Python loop over them. The attention is the
EXPANDED form only: every position's latent is up-projected to per-head keys and
values (in blocks of positions, so that a row of some 10,000 fits), and the
reference never absorbs ``W_ukv`` into the query — that the program's absorbed
decode read equals it is what the comparison shows. Equations, with ``h`` the
residual stream, ``p`` a token's position, ``eps`` the file's ``rms_norm_eps``
and ``R`` an RMS norm with a learned scale::

    h0 = E[tokens]
    a = R(h)
    c_q = R_qrank(W_dq a);  q = W_uq c_q, H heads of [q_nope (nope) | q_rope (rope)];  q_rope <- rotary(q_rope, p)
    [c | r] = W_dkv a  (kv_rank + rope);  c_kv = R_kvrank(c);  k_rope = rotary(r, p), one head that all H share
    [k_nope_h (nope) | v_h (v)] = W_ukv,h c_kv
    s_h(i, j) = (q_nope_h(i) . k_nope_h(j) + q_rope_h(i) . k_rope(j)) / sqrt(nope + rope),  j <= i
    h <- h + W_o [softmax_j(s_h) v_h]_h
    m = R(h)
    layer < first_k_dense_replace:  f = SwiGLU_intermediate(m)
    else:  s = sigmoid(W_r m) over all router_experts; sel = top-k of s + b
           w = s[sel] / (sum s[sel] + 1e-20) * routed_scaling_factor
           f = SwiGLU_shared(m) + sum over e in sel, e held here, of w_e SwiGLU_e(m)
    h <- h + f
    logits = W_head R(h)

**The share.** The configuration's ``n_routed_experts`` counts the experts held
here, ``experts_first`` says where they start among the router's
``router_experts`` outputs. The routing (scores, choice, weights) is over all of
them; what the absent experts would have added is left out, here as in the program.

Weights are the benchmark's own (``make_weights``): the harness hands the same
arrays to the program. The tree's layout is the interface both sides agree on::

    embed/embedding [V, D]; final_norm/scale [D]; lm_head/kernel [D, V]
    layer_i/{attn_norm,mlp_norm}/scale [D]
    layer_i/attn/q_down/kernel [D, q_rank]; q_norm/scale [q_rank]; q_up/kernel [q_rank, H*(nope+rope)]
    layer_i/attn/kv_down/kernel [D, kv_rank+rope]; kv_norm/scale [kv_rank]; kv_up/kernel [kv_rank, H*(nope+v)]
    layer_i/attn/o_proj/kernel [H*v, D]
    dense layers:  layer_i/mlp/{wg,wi,wo}/kernel
    expert layers: layer_i/shared/{wg,wi,wo}/kernel; layer_i/moe/router/kernel [D, router_experts];
                   layer_i/moe/router_bias [router_experts]; layer_i/moe/experts/{wg,wi}/kernel [held, D, F], wo [held, F, D]

Departures from the published model are listed in the configuration's file
(adjacent-pair rotary, the multi-token-prediction block not built); the reference follows the file.

``int8_weights=True`` is the lower-precision control: the same forward with every
matrix the program's int8 path would quantize (attention, dense, shared and
expert kernels and the head; not the embedding, not the router) rounded to
symmetric int8 with one scale an output channel, and an expert's own scales.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def shapes(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    d, h, v = cfg["hidden_size"], cfg["num_attention_heads"], cfg["vocab_size"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    ff, mf = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, routed = cfg["n_routed_experts"], cfg["router_experts"]
    swiglu = lambda width: {"wg": {"kernel": (d, width)}, "wi": {"kernel": (d, width)}, "wo": {"kernel": (width, d)}}  # noqa: E731
    tree: Dict[str, Any] = {"embed": {"embedding": (v, d)}, "final_norm": {"scale": (d,)}, "lm_head": {"kernel": (d, v)}}
    for i in range(cfg["num_hidden_layers"]):
        layer: Dict[str, Any] = {
            "attn_norm": {"scale": (d,)}, "mlp_norm": {"scale": (d,)},
            "attn": {
                "q_down": {"kernel": (d, qr)}, "q_norm": {"scale": (qr,)}, "q_up": {"kernel": (qr, h * (nope + rope))},
                "kv_down": {"kernel": (d, kvr + rope)}, "kv_norm": {"scale": (kvr,)}, "kv_up": {"kernel": (kvr, h * (nope + vd))},
                "o_proj": {"kernel": (h * vd, d)},
            },
        }
        if i < cfg["first_k_dense_replace"]:
            layer["mlp"] = swiglu(ff)
        else:
            layer["shared"] = swiglu(mf * cfg["n_shared_experts"])
            layer["moe"] = {
                "router": {"kernel": (d, routed)}, "router_bias": (routed,),
                "experts": {"wg": {"kernel": (held, d, mf)}, "wi": {"kernel": (held, d, mf)}, "wo": {"kernel": (held, mf, d)}},
            }
        tree[f"layer_{i}"] = layer
    return tree


def make_weights(cfg: Mapping[str, Any], seed: int, dtype: Any = jnp.bfloat16) -> Dict[str, Any]:
    """Seeded random weights, made on the device in one jitted call, in the type
    they are served in. Matrices are normal(0, 1/sqrt(fan_in)) (an up-projection's
    fan-in is its rank, an expert's its own); the embedding is normal(0, 1) (no
    multiplier: the stream the layers add to has unit scale); norm scales ones and
    the router's selection bias normal(0, 0.02) so that it changes some choices
    (both float32, as the program keeps them)."""
    tree = shapes(cfg)
    is_shape = lambda x: isinstance(x, tuple)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_shape)
    names = ["/".join(str(k.key) for k in path) for path, _ in flat]

    @jax.jit
    def build(key):
        out = []
        for i, (name, (_, shape)) in enumerate(zip(names, flat)):
            sub = jax.random.fold_in(key, i)
            if name.endswith("router_bias"):
                out.append(jax.random.normal(sub, shape, jnp.float32) * 0.02)
            elif len(shape) == 1:
                out.append(jnp.ones(shape, jnp.float32))
            else:
                std = 1.0 if name.startswith("embed") else shape[-2] ** -0.5
                out.append((jax.random.normal(sub, shape, jnp.float32) * std).astype(dtype))
        return out

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.tree_util.tree_unflatten(treedef, build(key))


def _matrix(a, int8):
    """A stored matrix ``[..., K, N]`` as float32; with ``int8`` rounded to 127 levels a side, a scale per
    output channel (the largest magnitude over the contraction axis), and read back."""
    a = a.astype(jnp.float32)
    if not int8:
        return a
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=-2, keepdims=True), 1e-8) / 127.0
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rope(x, positions, theta):
    """x [L, H, D]; rotates adjacent channel pairs (2i, 2i+1) by position * theta**(-2i/D)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def _swiglu(m, w, int8=False):
    mat = lambda a: _matrix(a, int8)  # noqa: E731
    return (jax.nn.silu(m @ mat(w["wg"]["kernel"])) * (m @ mat(w["wi"]["kernel"]))) @ mat(w["wo"]["kernel"])


def route(m, router, bias, *, top_k, normalize, scale):
    """``(chosen [L, k], weights [L, k])`` over all of the router's experts."""
    scores = jax.nn.sigmoid(m @ router.astype(jnp.float32))
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights * scale


@partial(jax.jit, static_argnames=("n_heads", "kv_rank", "nope", "rope", "v_dim", "theta", "eps", "block", "int8"))
def _attention(x, w, *, n_heads, kv_rank, nope, rope, v_dim, theta, eps, block, int8=False):
    """The attention half of a block on one sequence, expanded: x [L, D] float32."""
    with jax.default_matmul_precision("highest"):
        mat = lambda a: _matrix(a, int8)  # noqa: E731
        length = x.shape[0]
        positions = jnp.arange(length)
        attn = w["attn"]
        a = _rms_norm(x, w["attn_norm"]["scale"], eps)
        c_q = _rms_norm(a @ mat(attn["q_down"]["kernel"]), attn["q_norm"]["scale"], eps)
        q = (c_q @ mat(attn["q_up"]["kernel"])).reshape(length, n_heads, nope + rope)
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], positions, theta)
        down = a @ mat(attn["kv_down"]["kernel"])
        c_kv = _rms_norm(down[:, :kv_rank], attn["kv_norm"]["scale"], eps)
        k_rope = _rope(down[:, None, kv_rank:], positions, theta)[:, 0]  # [L, rope]: one head for all
        up = mat(attn["kv_up"]["kernel"])
        # every position's keys and values, up-projected a block of positions at a time
        kv = jnp.concatenate(
            [(c_kv[s : s + block] @ up).reshape(-1, n_heads, nope + v_dim) for s in range(0, length, block)], axis=0
        )
        k_nope, v = kv[..., :nope], kv[..., nope:]
        outs = []
        for start in range(0, length, block):  # query blocks bound the [H, q, keys] score tensor; later keys are never seen
            end = min(start + block, length)
            scores = jnp.einsum("qhd,shd->hqs", q_nope[start:end], k_nope[:end])
            scores = (scores + jnp.einsum("qhd,sd->hqs", q_rope[start:end], k_rope[:end])) * (nope + rope) ** -0.5
            visible = positions[None, :end] <= positions[start:end, None]
            scores = jnp.where(visible[None], scores, -jnp.inf)
            outs.append(jnp.einsum("hqs,shd->qhd", jax.nn.softmax(scores, axis=-1), v[:end]))
        out = jnp.concatenate(outs, axis=0).reshape(length, n_heads * v_dim)
        return x + out @ mat(attn["o_proj"]["kernel"])


@partial(jax.jit, static_argnames=("eps", "int8"))
def _dense_ffn(x, w, *, eps, int8=False):
    with jax.default_matmul_precision("highest"):
        return x + _swiglu(_rms_norm(x, w["mlp_norm"]["scale"], eps), w["mlp"], int8)


@partial(jax.jit, static_argnames=("eps", "top_k", "normalize", "scale", "int8"))
def _routing(x, w, *, eps, top_k, normalize, scale, int8=False):
    with jax.default_matmul_precision("highest"):
        m = _rms_norm(x, w["mlp_norm"]["scale"], eps)
        chosen, weights = route(m, w["moe"]["router"]["kernel"], w["moe"]["router_bias"], top_k=top_k,
                                normalize=normalize, scale=scale)
        return m, chosen, weights, _swiglu(m, w["shared"], int8)


@partial(jax.jit, static_argnames=("int8",))
def _one_expert(m, wg, wi, wo, weight, int8=False):
    """``weight [L]`` (zero where the token did not choose this expert) times the expert's SwiGLU."""
    with jax.default_matmul_precision("highest"):
        return weight[:, None] * ((jax.nn.silu(m @ _matrix(wg, int8)) * (m @ _matrix(wi, int8))) @ _matrix(wo, int8))


def expert_layer(x, w, cfg: Mapping[str, Any], int8: bool = False):
    """The feed-forward half of an expert layer on one sequence, the held experts one at a time.
    Returns ``(h, chosen [L, k])`` (the choice, for whoever counts the routing)."""
    m, chosen, weights, f = _routing(
        x, w, eps=float(cfg["rms_norm_eps"]), top_k=int(cfg["num_experts_per_tok"]), int8=int8,
        normalize=bool(cfg["norm_topk_prob"]), scale=float(cfg["routed_scaling_factor"]),
    )
    first = int(cfg.get("experts_first", 0))
    experts = w["moe"]["experts"]
    for local in range(int(cfg["n_routed_experts"])):
        weight = jnp.sum(jnp.where(chosen == first + local, weights, 0.0), axis=-1)
        f = f + _one_expert(m, experts["wg"]["kernel"][local], experts["wi"]["kernel"][local],
                            experts["wo"]["kernel"][local], weight, int8=int8)
    return x + f, chosen


@partial(jax.jit, static_argnames=("eps", "int8"))
def _head(x, rows, scale, kernel, *, eps, int8=False):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x[rows], scale, eps) @ _matrix(kernel, int8)


def hidden_states(weights: Mapping[str, Any], cfg: Mapping[str, Any], ids: np.ndarray, routing: Any = None,
                  int8_weights: bool = False):
    """The residual stream after the last layer, ``[len(ids), D]`` float32. ``routing``,
    a list, receives each expert layer's choice ``[L, k]``."""
    x = jnp.take(weights["embed"]["embedding"], jnp.asarray(ids), axis=0).astype(jnp.float32)
    eps = float(cfg["rms_norm_eps"])
    for i in range(cfg["num_hidden_layers"]):
        w = weights[f"layer_{i}"]
        x = _attention(
            x, w, n_heads=cfg["num_attention_heads"], kv_rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
            rope=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"], theta=float(cfg["rope_theta"]), eps=eps, block=1024,
            int8=int8_weights,
        )
        if i < cfg["first_k_dense_replace"]:
            x = _dense_ffn(x, w, eps=eps, int8=int8_weights)
        else:
            x, chosen = expert_layer(x, w, cfg, int8_weights)
            if routing is not None:
                routing.append(np.asarray(chosen))
    return x


def logits_at(weights: Mapping[str, Any], cfg: Mapping[str, Any], tokens: Sequence[int], rows: Sequence[int],
              pad_to: int = 512, int8_weights: bool = False) -> np.ndarray:
    """Logits ``[len(rows), vocab]`` (float32, on the host) of one full forward
    pass over ``tokens`` at sequence positions ``rows``. The sequence is padded
    on the right to a multiple of ``pad_to`` (causal attention never sees the
    padding, and one token's routing never depends on another's), so few shapes compile."""
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
