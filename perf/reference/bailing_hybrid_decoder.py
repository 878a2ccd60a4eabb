"""Plain reference for the bailing_hybrid decoder (Ling-3.0-flash): Kimi Delta Attention (KDA) layers beside
latent-attention (MLA) layers, given one chip's share of the routed experts.

Straight ``jax.numpy`` in float32 with matmuls at ``highest`` precision: no cache, no batching, no kernels,
nothing imported from the program. One sequence at a time, one layer at a time (each layer's bfloat16 weights
are upcast on the way in), the held experts by a Python loop over them. **KDA is the per-token recurrence in a
``lax.scan`` over positions**: no chunk form, no state handed between calls, so that the program's chunked
prefill and its one-step decode through the slot state are both compared with the rule as published. The MLA
layers are the EXPANDED form only (every position's latent up-projected to per-head keys and values). The norms,
the rotary, the SwiGLUs, the int8 rounding and the head are ``perf/reference/glm4_moe_lite_decoder.py``'s own
functions. Equations, with ``h`` the stream, ``t`` a position, ``R`` an RMS norm with a learned scale (eps the
file's ``rms_norm_eps``), ``a = R(h)``::

    h0 = E[tokens];  layer i is MLA where layer_types[i] == "mla", else KDA
    KDA (H heads, d = head_dim; per head unless said):
      conv(x)_t = sum_{j<4} taps[j] * x_{t-3+j}  (a channel; zeros before the sequence)
      q = l2norm(silu(conv(W_q a))) * d ** -0.5;  k = l2norm(silu(conv(W_k a)));  v = silu(conv(W_v a))
      g_t = kda_lower_bound * sigmoid(exp(A_log) * (W_f a + dt_bias))   (a vector of d: the log-decay)
      beta_t = sigmoid(W_b a)                                            (a scalar)
      S' = Diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t;  S_{-1} = 0
      h <- h + W_o [R_d(o_t) * sigmoid(W_g a)]          (one norm scale of d for all heads)
    MLA: q = W_q a, H heads of [q_nope | q_rope], q_rope <- rotary;  [c | r] = W_dkv a;  c_kv = R(c);
      k_rope = rotary(r), one head for all;  [k_nope_h | v_h] = W_ukv,h c_kv;
      s_h(i, j) = (q_nope_h(i) . k_nope_h(j) + q_rope_h(i) . k_rope(j)) / sqrt(nope + rope),  j <= i
      h <- h + W_o [softmax_j(s_h) v_h * sigmoid((W_gate a)_h)]_h
    m = R(h);  layer < first_k_dense_replace:  f = SwiGLU_intermediate(m)
    else:  s = sigmoid(W_r m) over all router_experts;  s' = s + b;  a group (router_experts / n_group consecutive
           experts) scores the sum of its two largest s';  the topk_group best groups stay;  sel = top-k of s' among them
           w = s[sel] / (sum s[sel] + 1e-20) * routed_scaling_factor
           f = SwiGLU_shared(m) + sum over e in sel, e held here, of w_e SwiGLU_e(m)
    h <- h + f;  logits = W_head R(h)

**The share** is the GLM reference's: ``num_experts`` counts the experts held here, ``experts_first`` says where
they start among the router's ``router_experts`` outputs; the routing is over all of them and the absent experts'
part is left out. The tree's layout is the interface program and reference agree on::

    embed/embedding [V, D]; final_norm/scale [D]; lm_head/kernel [D, V]; layer_i/{attn_norm,mlp_norm}/scale [D]
    KDA: layer_i/attn/{q_proj,k_proj,v_proj,f_proj,g_proj}/kernel [D, H*d]; b_proj/kernel [D, H]; o_proj/kernel [H*d, D]
         conv_taps [4, 3 (q, k, v), H*d]; A_log [H]; dt_bias [H*d]; o_norm/scale [d]
    MLA: layer_i/attn/q_proj/kernel [D, H*(nope+rope)]; kv_down/kernel [D, kv_rank+rope]; kv_norm/scale [kv_rank]
         kv_up/kernel [kv_rank, H*(nope+v)]; gate_proj/kernel [D, H]; o_proj/kernel [H*v, D]
    feed-forward: as the GLM reference's (mlp | shared, moe/router, moe/router_bias, moe/experts)

``int8_weights=True`` is the lower-precision control: every matrix named ``kernel`` but the router's rounded to
symmetric int8 with one scale an output channel (an expert's own); taps, biases and norms are left.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference.glm4_moe_lite_decoder import _dense_ffn, _head, _matrix, _one_expert, _rms_norm, _rope, _swiglu


def shapes(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    d, h, v = cfg["hidden_size"], cfg["num_attention_heads"], cfg["vocab_size"]
    hd, taps, kvr = cfg["head_dim"], cfg["short_conv_kernel_size"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    ff, mf = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, routed = cfg["num_experts"], cfg["router_experts"]
    swiglu = lambda width: {"wg": {"kernel": (d, width)}, "wi": {"kernel": (d, width)}, "wo": {"kernel": (width, d)}}  # noqa: E731
    wide = {"kernel": (d, h * hd)}
    kda = {
        "q_proj": wide, "k_proj": wide, "v_proj": wide, "f_proj": wide, "g_proj": wide, "b_proj": {"kernel": (d, h)},
        "o_proj": {"kernel": (h * hd, d)}, "conv_taps": (taps, 3, h * hd), "A_log": (h,), "dt_bias": (h * hd,),
        "o_norm": {"scale": (hd,)},
    }
    mla = {
        "q_proj": {"kernel": (d, h * (nope + rope))}, "kv_down": {"kernel": (d, kvr + rope)}, "kv_norm": {"scale": (kvr,)},
        "kv_up": {"kernel": (kvr, h * (nope + vd))}, "gate_proj": {"kernel": (d, h)}, "o_proj": {"kernel": (h * vd, d)},
    }
    tree: Dict[str, Any] = {"embed": {"embedding": (v, d)}, "final_norm": {"scale": (d,)}, "lm_head": {"kernel": (d, v)}}
    for i, kind in enumerate(cfg["layer_types"]):
        layer: Dict[str, Any] = {"attn_norm": {"scale": (d,)}, "mlp_norm": {"scale": (d,)}, "attn": dict(kda if kind == "kda" else mla)}
        if i < cfg["first_k_dense_replace"]:
            layer["mlp"] = swiglu(ff)
        else:
            layer["shared"] = swiglu(mf * cfg["num_shared_experts"])
            layer["moe"] = {
                "router": {"kernel": (d, routed)}, "router_bias": (routed,),
                "experts": {"wg": {"kernel": (held, d, mf)}, "wi": {"kernel": (held, d, mf)}, "wo": {"kernel": (held, mf, d)}},
            }
        tree[f"layer_{i}"] = layer
    return tree


def make_weights(cfg: Mapping[str, Any], seed: int, dtype: Any = jnp.bfloat16) -> Dict[str, Any]:
    """Seeded random weights, made on the device in one jitted call, in the type they are served in. Matrices
    normal(0, 1/sqrt(fan_in)) (an expert's fan-in its own); the embedding normal(0, 1); the convolutions' taps
    normal(0, 1/2) (four taps a channel); norm scales ones; the router's selection bias normal(0, 0.02); and the
    decay gate's two parameters so that a head's channels forget over spans from a few tokens to thousands:
    ``A_log = log u``, ``u ~ U(1, 4)`` a head and ``dt_bias ~ U(-6, -1.5)`` a channel, so that ``exp(g)`` lies
    between ~0.4 and 1 - 1e-10 for a unit-scale ``W_f a`` (vectors and the bias float32, as the program keeps them)."""
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
            elif name.endswith("A_log"):
                out.append(jnp.log(jax.random.uniform(sub, shape, jnp.float32, 1.0, 4.0)))
            elif name.endswith("dt_bias"):
                out.append(jax.random.uniform(sub, shape, jnp.float32, -6.0, -1.5))
            elif name.endswith("conv_taps"):
                out.append((jax.random.normal(sub, shape, jnp.float32) * shape[0] ** -0.5).astype(dtype))
            elif len(shape) == 1:
                out.append(jnp.ones(shape, jnp.float32))
            else:
                std = 1.0 if name.startswith("embed") else shape[-2] ** -0.5
                out.append((jax.random.normal(sub, shape, jnp.float32) * std).astype(dtype))
        return out

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.tree_util.tree_unflatten(treedef, build(key))


def route(m, router, bias, *, top_k, normalize, scale, n_group, topk_group):
    """``(chosen [L, k], weights [L, k])`` over all of the router's experts, the choice limited to the best groups:
    a plain loop over the groups, no reshape of the scores."""
    scores = jax.nn.sigmoid(m @ router.astype(jnp.float32))
    biased = scores + bias.astype(jnp.float32)
    size = biased.shape[-1] // n_group
    group_scores = jnp.stack(
        [jnp.sum(jnp.sort(biased[:, g * size : (g + 1) * size], axis=-1)[:, -2:], axis=-1) for g in range(n_group)], axis=-1
    )
    threshold = jnp.sort(group_scores, axis=-1)[:, -topk_group][:, None]  # the topk_group-th best group's score
    stays = jnp.repeat(group_scores >= threshold, size, axis=-1)
    _, chosen = jax.lax.top_k(jnp.where(stays, biased, -jnp.inf), top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights * scale


@partial(jax.jit, static_argnames=("n_heads", "head_dim", "bound", "eps", "int8"))
def _kda(x, w, *, n_heads, head_dim, bound, eps, int8=False):
    """The KDA half of a block on one sequence: x [L, D] float32; the recurrence a position at a time."""
    with jax.default_matmul_precision("highest"):
        mat = lambda name: _matrix(w["attn"][name]["kernel"], int8)  # noqa: E731
        attn, length = w["attn"], x.shape[0]
        a = _rms_norm(x, w["attn_norm"]["scale"], eps)
        taps = attn["conv_taps"].astype(jnp.float32)  # [4, 3, H * d]

        def conv(rows, which):
            padded = jnp.concatenate([jnp.zeros((taps.shape[0] - 1, rows.shape[1]), jnp.float32), rows], axis=0)
            mixed = sum(padded[j : j + length] * taps[j, which] for j in range(taps.shape[0]))
            return jax.nn.silu(mixed).reshape(length, n_heads, head_dim)

        unit = lambda t: t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
        q = unit(conv(a @ mat("q_proj"), 0)) * head_dim ** -0.5
        k = unit(conv(a @ mat("k_proj"), 1))
        v = conv(a @ mat("v_proj"), 2)
        rate = jnp.exp(attn["A_log"])[:, None] * (a @ mat("f_proj") + attn["dt_bias"]).reshape(length, n_heads, head_dim)
        g = bound * jax.nn.sigmoid(rate)
        beta = jax.nn.sigmoid(a @ mat("b_proj"))  # [L, H]

        def token(state, xs):  # state [H, d, d]: key channel x value channel
            q_t, k_t, v_t, g_t, beta_t = xs
            state = jnp.exp(g_t)[:, :, None] * state
            held = jnp.einsum("hkv,hk->hv", state, k_t)
            state = state + beta_t[:, None, None] * jnp.einsum("hk,hv->hkv", k_t, v_t - held)
            return state, jnp.einsum("hkv,hk->hv", state, q_t)

        _, out = jax.lax.scan(token, jnp.zeros((n_heads, head_dim, head_dim), jnp.float32), (q, k, v, g, beta))
        out = _rms_norm(out, attn["o_norm"]["scale"], eps) * jax.nn.sigmoid(a @ mat("g_proj")).reshape(length, n_heads, head_dim)
        return x + out.reshape(length, n_heads * head_dim) @ mat("o_proj")


@partial(jax.jit, static_argnames=("n_heads", "kv_rank", "nope", "rope", "v_dim", "theta", "eps", "block", "int8"))
def _mla(x, w, *, n_heads, kv_rank, nope, rope, v_dim, theta, eps, block, int8=False):
    """The MLA half of a block on one sequence, expanded: x [L, D] float32. No query bottleneck; a gate a head."""
    with jax.default_matmul_precision("highest"):
        mat = lambda name: _matrix(w["attn"][name]["kernel"], int8)  # noqa: E731
        length = x.shape[0]
        positions = jnp.arange(length)
        a = _rms_norm(x, w["attn_norm"]["scale"], eps)
        q = (a @ mat("q_proj")).reshape(length, n_heads, nope + rope)
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], positions, theta)
        down = a @ mat("kv_down")
        c_kv = _rms_norm(down[:, :kv_rank], w["attn"]["kv_norm"]["scale"], eps)
        k_rope = _rope(down[:, None, kv_rank:], positions, theta)[:, 0]  # [L, rope]: one head for all
        up = mat("kv_up")
        kv = jnp.concatenate(
            [(c_kv[s : s + block] @ up).reshape(-1, n_heads, nope + v_dim) for s in range(0, length, block)], axis=0
        )
        k_nope, v = kv[..., :nope], kv[..., nope:]
        outs = []
        for start in range(0, length, block):  # query blocks bound the [H, q, keys] score tensor
            end = min(start + block, length)
            scores = jnp.einsum("qhd,shd->hqs", q_nope[start:end], k_nope[:end])
            scores = (scores + jnp.einsum("qhd,sd->hqs", q_rope[start:end], k_rope[:end])) * (nope + rope) ** -0.5
            visible = positions[None, :end] <= positions[start:end, None]
            scores = jnp.where(visible[None], scores, -jnp.inf)
            outs.append(jnp.einsum("hqs,shd->qhd", jax.nn.softmax(scores, axis=-1), v[:end]))
        out = jnp.concatenate(outs, axis=0) * jax.nn.sigmoid(a @ mat("gate_proj"))[:, :, None]
        return x + out.reshape(length, n_heads * v_dim) @ mat("o_proj")


@partial(jax.jit, static_argnames=("eps", "top_k", "normalize", "scale", "n_group", "topk_group", "int8"))
def _routing(x, w, *, eps, top_k, normalize, scale, n_group, topk_group, int8=False):
    with jax.default_matmul_precision("highest"):
        m = _rms_norm(x, w["mlp_norm"]["scale"], eps)
        chosen, weights = route(m, w["moe"]["router"]["kernel"], w["moe"]["router_bias"], top_k=top_k,
                                normalize=normalize, scale=scale, n_group=n_group, topk_group=topk_group)
        return m, chosen, weights, _swiglu(m, w["shared"], int8)


def expert_layer(x, w, cfg: Mapping[str, Any], int8: bool = False):
    """The feed-forward half of an expert layer on one sequence, the held experts one at a time.
    Returns ``(h, chosen [L, k])`` (the choice, for whoever counts the routing)."""
    m, chosen, weights, f = _routing(
        x, w, eps=float(cfg["rms_norm_eps"]), top_k=int(cfg["num_experts_per_tok"]), int8=int8,
        normalize=bool(cfg["norm_topk_prob"]), scale=float(cfg["routed_scaling_factor"]),
        n_group=int(cfg["n_group"]), topk_group=int(cfg["topk_group"]),
    )
    first = int(cfg.get("experts_first", 0))
    experts = w["moe"]["experts"]
    for local in range(int(cfg["num_experts"])):
        weight = jnp.sum(jnp.where(chosen == first + local, weights, 0.0), axis=-1)
        f = f + _one_expert(m, experts["wg"]["kernel"][local], experts["wi"]["kernel"][local],
                            experts["wo"]["kernel"][local], weight, int8=int8)
    return x + f, chosen


def hidden_states(weights: Mapping[str, Any], cfg: Mapping[str, Any], ids: np.ndarray, routing: Any = None,
                  int8_weights: bool = False):
    """The residual stream after the last layer, ``[len(ids), D]`` float32. ``routing``,
    a list, receives each expert layer's choice ``[L, k]``."""
    x = jnp.take(weights["embed"]["embedding"], jnp.asarray(ids), axis=0).astype(jnp.float32)
    eps = float(cfg["rms_norm_eps"])
    for i, kind in enumerate(cfg["layer_types"]):
        w = weights[f"layer_{i}"]
        if kind == "kda":
            x = _kda(x, w, n_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
                     bound=float(cfg["kda_lower_bound"]), eps=eps, int8=int8_weights)
        else:
            x = _mla(
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
    """Logits ``[len(rows), vocab]`` (float32, on the host) of one full forward pass over ``tokens`` at sequence
    positions ``rows``. The sequence is padded on the right to a multiple of ``pad_to`` (neither the recurrence nor
    causal attention ever sees what follows a position, and one token's routing never depends on another's), so
    few shapes compile."""
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
