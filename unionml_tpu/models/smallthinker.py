"""The SmallThinker decoder (PowerInfer's SmallThinker-21BA3B): plain grouped-query
attention, on most layers over a sliding window with rotary embeddings and on the
others global with no positional signal at all, and on every layer a softmax-routed
layer of ReGLU experts whose router reads the block's input, before attention.

Follows :class:`~unionml_tpu.models.llama.Llama`'s cache contract, so
:class:`~unionml_tpu.models.generate.Generator` and the serving engine drive it
unchanged; both kinds of layer keep keys and values in one paged cache, one pool
and one block table a layer. One block, with ``h`` the residual stream::

    r = route(h)                       # softmax over the router's logits, top-k, renormalised
    h = h + attn(attn_norm(h))         # a sliding layer: window + rotary; a global one: neither
    h = h + experts_r(mlp_norm(h))     # the chosen experts' ReGLU, weighted as routed

``sliding_window_layout`` and ``rope_layout`` (one flag a layer, as published)
choose each layer's window and rotary apart; the published model's agree (layer
``4j`` global and position-free, the three after it windowed and rotated). There
is no shared expert and no dense layer. The config states no place for the router
nor the experts' activation: :attr:`SmallThinkerConfig.route_before_attention` and
``activation`` default to the readings the benchmark's configuration file lists
under ``assumed`` (the pre-attention router; ReGLU).

**The share** is :mod:`unionml_tpu.models.afmoe`'s: ``n_experts`` is the router's
width, ``experts_held = (first, count)`` the experts whose weights this module
holds (all of them by default, as a pipeline stage holds them).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from unionml_tpu.models.glm4_moe_lite import Glm4MoeLiteTransformer
from unionml_tpu.models.layers import WINDOW_COUNTERS, Attention, RMSNorm
from unionml_tpu.models.moe import MOE_COUNTERS, ExpertShare

#: the published period: layer ``4j`` global and position-free, ``4j + 1 .. 4j + 3`` windowed and rotated
PERIOD = (0, 1, 1, 1)


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    dim: int = 2560
    n_layers: int = 52
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    moe_hidden_dim: int = 768  # one expert's width
    n_experts: int = 64  # the router's width
    experts_held: Optional[Tuple[int, int]] = None  # (first, count) held here; None: all
    k: int = 6
    route_norm: bool = True  # the chosen experts' softmax weights renormalised over the k
    #: where the router reads: the block's input (True), or the experts' own input, mlp_norm(h) (False)
    route_before_attention: bool = True
    activation: str = "relu"  # the experts' gate: "relu" (ReGLU) or "silu" (SwiGLU)
    sliding_window: int = 4096
    #: a flag a layer: 1 windowed, 0 global; None: the published period repeated
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    #: a flag a layer: 1 rotary, 0 none; None: the published period repeated
    rope_layout: Optional[Tuple[int, ...]] = None
    rope_theta: float = 1500000.0
    norm_eps: float = 1e-6
    max_seq_len: int = 16384
    attention_impl: str = "auto"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self) -> None:
        held = (0, self.n_experts) if self.experts_held is None else tuple(int(v) for v in self.experts_held)
        object.__setattr__(self, "experts_held", held)
        for name in ("sliding_window_layout", "rope_layout"):
            flags = getattr(self, name)
            flags = tuple(PERIOD[i % len(PERIOD)] for i in range(self.n_layers)) if flags is None else tuple(int(f) for f in flags)
            if len(flags) != self.n_layers or set(flags) - {0, 1}:
                raise ValueError(f"{name} must give {self.n_layers} flags of 0 or 1, got {flags}")
            object.__setattr__(self, name, flags)

    @classmethod
    def tiny(cls, **overrides: Any) -> "SmallThinkerConfig":
        """Test scale: one whole period (global, then three windowed layers), a window of 8."""
        defaults = dict(
            vocab_size=256, dim=64, n_layers=4, n_heads=7, n_kv_heads=1, head_dim=16, moe_hidden_dim=32, n_experts=8,
            k=3, sliding_window=8, max_seq_len=128,
        )
        defaults.update(overrides)
        return cls(**defaults)


class SmallThinkerBlock(nn.Module):
    """One decoder block (module docstring); ``index`` picks its window and its rotary."""

    config: SmallThinkerConfig
    index: int

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: Optional[jax.Array] = None,
        mask: Optional[jax.Array] = None,
        cache: Optional[Any] = None,
        token_mask: Optional[jax.Array] = None,
    ) -> Any:
        cfg = self.config
        sliding = bool(cfg.sliding_window_layout[self.index])
        norm = lambda name: RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)  # noqa: E731
        block_input = x
        with jax.named_scope("smallthinker.attn_window" if sliding else "smallthinker.attn_full"):
            attn_out = Attention(
                n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim,
                causal=True,
                rope=bool(cfg.rope_layout[self.index]),
                rope_theta=cfg.rope_theta,
                impl=cfg.attention_impl,
                window=cfg.sliding_window if sliding else None,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                name="attn",
            )(norm("attn_norm")(x), positions, mask, cache, token_mask)
        if cache is not None:
            attn_out, cache = attn_out
        x = x + attn_out
        m = norm("mlp_norm")(x)
        x = x + ExpertShare(
            n_experts=cfg.n_experts,
            experts_held=cfg.experts_held,
            hidden_dim=cfg.moe_hidden_dim,
            k=cfg.k,
            route_norm=cfg.route_norm,
            score="softmax",
            activation=cfg.activation,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="moe",
        )(m, token_mask, router_input=block_input if cfg.route_before_attention else None)
        return (x, cache) if cache is not None else x


class SmallThinkerTransformer(Glm4MoeLiteTransformer):
    """Causal LM: tokens ``[B, L]`` -> logits ``[B, L, vocab]`` (untied head):
    :class:`~unionml_tpu.models.glm4_moe_lite.Glm4MoeLiteTransformer`'s shell (embedding, blocks, final norm,
    head, ``token_mask``) around :class:`SmallThinkerBlock`. Counts the routing's :data:`MOE_COUNTERS`, the
    sliding layers' decode reads (:data:`~unionml_tpu.models.layers.WINDOW_COUNTERS`, with the pages they
    skipped) and the chunks' key positions."""

    config: SmallThinkerConfig

    counters = MOE_COUNTERS + WINDOW_COUNTERS + ("decode_window_pages_skipped", "kv_positions_attended", "kv_positions_needed")
    counter_views = {"moe": MOE_COUNTERS, "window": WINDOW_COUNTERS}  # the serving engine's stats()["moe"], ["window"]
    block = SmallThinkerBlock
