"""Plain reference for fine-tuning a pre-norm BERT-shaped encoder with AdamW.

Straight ``jax.numpy`` in float32 with matmuls at ``highest`` precision: forward,
softmax cross-entropy, gradients by ``jax.grad`` of this file's own forward, and a
hand-written AdamW. Nothing is imported from the program or from optax. The batch
is worked through in blocks of rows (the gradient of the mean loss is the mean
of the blocks' gradients), so a step at the timed batch fits beside nothing else.

The parameter tree's layout is the interface both sides agree on (the harness
makes the weights with ``make_weights`` and hands the same arrays to the program)::

    tok_embed/embedding [V, D]; pos_embed/embedding [P, D]; embed_norm/{scale,bias};
    layer_i/{attn_norm,mlp_norm}/{scale,bias}; layer_i/attn/{q,k,v,o}_proj/kernel;
    layer_i/mlp/{wi,wo}/kernel; pooler/{kernel,bias}; classifier/{kernel,bias}

``quant="int8"`` rounds both operands of every matmul to symmetric int8 (per
row of the contraction, straight-through gradient): the lower-precision control.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def shapes(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    norm = {"scale": (d,), "bias": (d,)}
    layer = {
        "attn_norm": norm,
        "attn": {name: {"kernel": (d, d)} for name in ("q_proj", "k_proj", "v_proj", "o_proj")},
        "mlp_norm": norm,
        "mlp": {"wi": {"kernel": (d, ff)}, "wo": {"kernel": (ff, d)}},
    }
    tree: Dict[str, Any] = {
        "tok_embed": {"embedding": (cfg["vocab_size"], d)},
        "pos_embed": {"embedding": (cfg["max_position_embeddings"], d)},
        "embed_norm": norm,
        "pooler": {"kernel": (d, d), "bias": (d,)},
        "classifier": {"kernel": (d, cfg["num_labels"]), "bias": (cfg["num_labels"],)},
    }
    for i in range(cfg["num_hidden_layers"]):
        tree[f"layer_{i}"] = layer
    return tree


def make_weights(cfg: Mapping[str, Any], seed: int) -> Dict[str, Any]:
    """Seeded float32 weights in one jitted call: embeddings normal(0, 0.02),
    matrices normal(0, 1/sqrt(fan_in)), biases zeros, norm scales ones."""
    tree = shapes(cfg)
    is_shape = lambda x: isinstance(x, tuple)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_shape)

    @jax.jit
    def build(key):
        out = []
        for i, (path, shape) in enumerate(flat):
            name = str(path[-1])
            k = jax.random.fold_in(key, i)
            if "scale" in name:
                out.append(jnp.ones(shape, jnp.float32))
            elif "bias" in name:
                out.append(jnp.zeros(shape, jnp.float32))
            elif "embedding" in name:
                out.append(jax.random.normal(k, shape, jnp.float32) * 0.02)
            else:
                out.append(jax.random.normal(k, shape, jnp.float32) * shape[0] ** -0.5)
        return out

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.tree_util.tree_unflatten(treedef, build(key))


def _fake_int8(x, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _matmul(x, w, quant):
    if quant == "int8":
        return _fake_int8(x, -1) @ _fake_int8(w, 0)
    if quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return x @ w


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def forward(params, tokens, *, n_layers, n_heads, eps, quant=None):
    """tokens [B, L] -> class logits [B, C]."""
    batch, length = tokens.shape
    x = params["tok_embed"]["embedding"][tokens] + params["pos_embed"]["embedding"][:length][None]
    x = _layer_norm(x, params["embed_norm"], eps)
    head_dim = x.shape[-1] // n_heads
    for i in range(n_layers):
        p = params[f"layer_{i}"]
        h = _layer_norm(x, p["attn_norm"], eps)
        split = lambda a: a.reshape(batch, length, n_heads, head_dim)  # noqa: E731
        q = split(_matmul(h, p["attn"]["q_proj"]["kernel"], quant))
        k = split(_matmul(h, p["attn"]["k_proj"]["kernel"], quant))
        v = split(_matmul(h, p["attn"]["v_proj"]["kernel"], quant))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * head_dim**-0.5
        attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
        x = x + _matmul(attn.reshape(batch, length, -1), p["attn"]["o_proj"]["kernel"], quant)
        h = _layer_norm(x, p["mlp_norm"], eps)
        h = jax.nn.gelu(_matmul(h, p["mlp"]["wi"]["kernel"], quant), approximate=True)
        x = x + _matmul(h, p["mlp"]["wo"]["kernel"], quant)
    pooled = jnp.tanh(_matmul(x[:, 0], params["pooler"]["kernel"], quant) + params["pooler"]["bias"])
    return _matmul(pooled, params["classifier"]["kernel"], quant) + params["classifier"]["bias"]


def _block_loss(params, tokens, labels, static):
    logits = forward(params, tokens, **static)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=1))


@partial(jax.jit, static_argnames=("static",))
def _block_grad(params, tokens, labels, static):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(_block_loss)(params, tokens, labels, dict(static))


def loss_and_grad(params, cfg: Mapping[str, Any], tokens: np.ndarray, labels: np.ndarray, row_block: int,
                  quant: Optional[str] = None) -> Tuple[float, Any]:
    """Mean loss over the batch and its gradient, accumulated over blocks of rows."""
    static = tuple(sorted(dict(
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"], eps=float(cfg["layer_norm_eps"]), quant=quant,
    ).items()))
    n = tokens.shape[0]
    total, grads = 0.0, None
    for start in range(0, n, row_block):
        loss, g = _block_grad(params, jnp.asarray(tokens[start : start + row_block]),
                              jnp.asarray(labels[start : start + row_block], jnp.int32), static)
        total += float(loss)
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
    return total / n, jax.tree_util.tree_map(lambda g: g / n, grads)


@jax.jit
def _adamw(params, mu, nu, grads, step, lr, b1, b2, eps, wd):
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1**step, 1 - b2**step
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p), params, mu, nu
    )
    return params, mu, nu


def train(params, cfg: Mapping[str, Any], trainer: Mapping[str, Any], batches, row_block: int,
          quant: Optional[str] = None) -> Dict[str, Any]:
    """Run ``len(batches)`` AdamW steps from ``params``; returns each step's loss,
    the first gradient and the final parameters."""
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    mu, nu = zeros, zeros
    losses, first_grad = [], None
    for step, (tokens, labels) in enumerate(batches, start=1):
        loss, grads = loss_and_grad(params, cfg, tokens, labels, row_block, quant)
        losses.append(loss)
        if first_grad is None:
            first_grad = grads
        params, mu, nu = _adamw(
            params, mu, nu, grads, jnp.float32(step), jnp.float32(trainer["learning_rate"]),
            jnp.float32(trainer["b1"]), jnp.float32(trainer["b2"]), jnp.float32(trainer["eps"]),
            jnp.float32(trainer["weight_decay"]),
        )
    return {"losses": losses, "first_grad": first_grad, "params": params}
