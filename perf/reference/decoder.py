"""Plain reference for a pre-norm RoPE / grouped-query / SwiGLU causal decoder.

Straight ``jax.numpy`` in float32 with matmuls at ``highest`` precision: no
cache, no batching, no kernels, nothing imported from the program. One sequence
at a time, one layer at a time (each layer's bfloat16 weights are upcast on the
way in), so sixteen layers at float32 never sit in memory together.

Weights are the benchmark's own (``make_weights``): the harness hands the same
arrays to the program, never the other way round. The tree's layout is the
interface both sides agree on::

    embed/embedding [V, D]; layer_i/{attn_norm,mlp_norm}/scale [D];
    layer_i/attn/{q_proj,k_proj,v_proj,o_proj}/kernel; layer_i/mlp/{wg,wi,wo}/kernel;
    final_norm/scale [D]; lm_head/kernel [D, V]

Departures from the published model are listed in the configuration's file
(``rms_norm_eps``, adjacent-pair rotary); the reference follows the file.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def shapes(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv, ff, v = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["intermediate_size"], cfg["vocab_size"]
    layer = {
        "attn_norm": {"scale": (d,)},
        "attn": {
            "q_proj": {"kernel": (d, h * hd)}, "k_proj": {"kernel": (d, kv * hd)},
            "v_proj": {"kernel": (d, kv * hd)}, "o_proj": {"kernel": (h * hd, d)},
        },
        "mlp_norm": {"scale": (d,)},
        "mlp": {"wg": {"kernel": (d, ff)}, "wi": {"kernel": (d, ff)}, "wo": {"kernel": (ff, d)}},
    }
    tree: Dict[str, Any] = {"embed": {"embedding": (v, d)}, "final_norm": {"scale": (d,)}, "lm_head": {"kernel": (d, v)}}
    for i in range(cfg["num_hidden_layers"]):
        tree[f"layer_{i}"] = layer
    return tree


def make_weights(cfg: Mapping[str, Any], seed: int, dtype: Any = jnp.bfloat16) -> Dict[str, Any]:
    """Seeded random weights, made on the device in one jitted call, in the type
    they are served in. Matrices are normal(0, 1/sqrt(fan_in)), the embedding
    normal(0, 1), norm scales ones (float32, as the program keeps them)."""
    tree = shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def build(key):
        out = []
        for i, shape in enumerate(leaves):
            if len(shape) == 1:
                out.append(jnp.ones(shape, jnp.float32))
                continue
            std = 1.0 if i == embed_index else shape[0] ** -0.5
            out.append((jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * std).astype(dtype))
        return out

    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(tree, is_leaf=lambda x: isinstance(x, tuple))[0]]
    embed_index = next(i for i, p in enumerate(paths) if "embed" in str(p[0]))
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.tree_util.tree_unflatten(treedef, build(key))


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """x [L, H, D]; rotates adjacent channel pairs (2i, 2i+1) by position * theta**(-2i/D)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "theta", "eps", "q_block"))
def _layer(x, w, *, n_heads, n_kv, head_dim, theta, eps, q_block):
    """One block on one sequence: x [L, D] float32."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        length = x.shape[0]
        positions = jnp.arange(length)
        h = _rms_norm(x, f32(w["attn_norm"]["scale"]), eps)
        q = (h @ f32(w["attn"]["q_proj"]["kernel"])).reshape(length, n_heads, head_dim)
        k = (h @ f32(w["attn"]["k_proj"]["kernel"])).reshape(length, n_kv, head_dim)
        v = (h @ f32(w["attn"]["v_proj"]["kernel"])).reshape(length, n_kv, head_dim)
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        group = n_heads // n_kv
        q = q.reshape(length, n_kv, group, head_dim)
        outs = []
        for start in range(0, length, q_block):  # query blocks bound the [H, q, L] score tensor
            qb = q[start : start + q_block]
            scores = jnp.einsum("qkgd,skd->kgqs", qb, k) * head_dim**-0.5
            visible = positions[None, :] <= positions[start : start + q_block, None]
            scores = jnp.where(visible[None, None], scores, -jnp.inf)
            weights = jax.nn.softmax(scores, axis=-1)
            outs.append(jnp.einsum("kgqs,skd->qkgd", weights, v))
        attn = jnp.concatenate(outs, axis=0).reshape(length, n_heads * head_dim)
        x = x + attn @ f32(w["attn"]["o_proj"]["kernel"])
        h = _rms_norm(x, f32(w["mlp_norm"]["scale"]), eps)
        gate = jax.nn.silu(h @ f32(w["mlp"]["wg"]["kernel"]))
        up = h @ f32(w["mlp"]["wi"]["kernel"])
        return x + (gate * up) @ f32(w["mlp"]["wo"]["kernel"])


@partial(jax.jit, static_argnames=("eps",))
def _head(x, rows, scale, kernel, *, eps):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x[rows], scale.astype(jnp.float32), eps)
        return h @ kernel.astype(jnp.float32)


def logits_at(weights: Mapping[str, Any], cfg: Mapping[str, Any], tokens: Sequence[int], rows: Sequence[int],
              pad_to: int = 512) -> np.ndarray:
    """Logits ``[len(rows), vocab]`` (float32, on the host) of one full forward
    pass over ``tokens`` at sequence positions ``rows``. The sequence is padded
    on the right to a multiple of ``pad_to`` (causal attention never sees the
    padding), so few shapes compile."""
    n = len(tokens)
    width = -(-n // pad_to) * pad_to
    ids = np.zeros((width,), np.int32)
    ids[:n] = np.asarray(tokens, np.int32)
    x = jnp.take(weights["embed"]["embedding"], jnp.asarray(ids), axis=0).astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(
            x, weights[f"layer_{i}"], n_heads=cfg["num_attention_heads"], n_kv=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]), q_block=1024,
        )
    row_ids = np.zeros((-(-len(rows) // 64) * 64,), np.int32)
    row_ids[: len(rows)] = np.asarray(rows, np.int32)
    out = _head(x, jnp.asarray(row_ids), weights["final_norm"]["scale"], weights["lm_head"]["kernel"],
                eps=float(cfg["rms_norm_eps"]))
    return np.asarray(out)[: len(rows)]
