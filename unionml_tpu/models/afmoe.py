"""The afmoe decoder (Arcee Trinity family): gated attention with per-head q/k
norms, sliding-window layers with rotary embeddings and every n-th layer global
without them, four norms a block, and — after a few leading dense layers — a
shared expert beside sigmoid-routed experts of which this chip may hold a share.

Follows :class:`~unionml_tpu.models.llama.Llama`'s cache contract, so
:class:`~unionml_tpu.models.generate.Generator` and the serving engines drive it
unchanged; layers of both kinds live in one paged cache, one pool and one block
table a layer. One block, with ``h`` the residual stream::

    a = attn(attn_norm(h));  h = h + post_attn_norm(a)
    f = ffn(mlp_norm(h));    h = h + post_mlp_norm(f)

where ``attn`` is :class:`~unionml_tpu.models.layers.Attention` with ``qk_norm``
and ``gated`` on (and ``window`` + rotary on a sliding layer, neither on a full
one), and ``ffn`` is a SwiGLU :class:`~unionml_tpu.models.layers.MLP` on the
first ``n_dense_layers`` layers and ``shared(m) + moe(m)`` after them:
a shared SwiGLU expert every token takes, plus :class:`~unionml_tpu.models.moe.ExpertShare`.

**The share.** ``n_experts`` is the router's width — every expert of the model —
and ``experts_held = (first, count)`` the experts whose weights this module
holds (all of them by default). The routed sum runs over the held experts only;
in an expert-parallel deployment the other chips add theirs. The shared expert,
attention and the router are computed alike on every chip.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from unionml_tpu.models.layers import MLP, Attention, IotaEmbed, RMSNorm
from unionml_tpu.models.moe import MOE_COUNTERS, ExpertShare
from unionml_tpu.parallel.sharding import PartitionRules

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    dim: int = 3072
    n_layers: int = 60
    n_heads: int = 48
    n_kv_heads: int = 8
    head_dim: int = 128
    hidden_dim: int = 12288  # the leading dense layers' SwiGLU width
    moe_hidden_dim: int = 3072  # one expert's (routed or shared) SwiGLU width
    n_experts: int = 256  # the router's width: every routed expert of the model
    experts_held: Optional[Tuple[int, int]] = None  # (first, count) held here; None: all
    k: int = 4
    n_shared_experts: int = 1
    n_dense_layers: int = 6
    #: each layer's attention kind; None: sliding with every ``global_every``-th layer full
    layer_types: Optional[Tuple[str, ...]] = None
    global_every: int = 4
    sliding_window: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.448
    mup_enabled: bool = True  # the embedding is scaled by sqrt(dim)
    max_seq_len: int = 262144
    attention_impl: str = "auto"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self) -> None:
        if self.score_func != "sigmoid":
            raise ValueError(f"score_func {self.score_func!r}: afmoe scores its experts by sigmoid")
        kinds = self.layer_types
        if kinds is None:
            kinds = tuple(FULL if (i + 1) % self.global_every == 0 else SLIDING for i in range(self.n_layers))
        kinds = tuple(kinds)
        if len(kinds) != self.n_layers or set(kinds) - {SLIDING, FULL}:
            raise ValueError(f"layer_types must name {self.n_layers} layers as {SLIDING!r} or {FULL!r}")
        object.__setattr__(self, "layer_types", kinds)
        held = (0, self.n_experts) if self.experts_held is None else tuple(int(v) for v in self.experts_held)
        object.__setattr__(self, "experts_held", held)

    @classmethod
    def tiny(cls, **overrides: Any) -> "AfmoeConfig":
        """Test scale: one dense layer, then a whole period (sliding x3, full) of expert layers."""
        defaults = dict(
            vocab_size=256, dim=64, n_layers=5, n_heads=4, n_kv_heads=2, head_dim=16, hidden_dim=128,
            moe_hidden_dim=32, n_experts=8, k=2, n_dense_layers=1, sliding_window=8, max_seq_len=128,
            layer_types=(SLIDING, SLIDING, SLIDING, SLIDING, FULL),
        )
        defaults.update(overrides)
        return cls(**defaults)


class AfmoeBlock(nn.Module):
    """One decoder block (module docstring); ``index`` picks its attention kind and its feed-forward."""

    config: AfmoeConfig
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
        sliding = cfg.layer_types[self.index] == SLIDING
        norm = lambda name: RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)  # noqa: E731
        swiglu = lambda width, name: MLP(hidden_dim=width, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)  # noqa: E731
        attn_out = Attention(
            n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim,
            causal=True,
            rope=sliding,  # the full layers carry no positional signal of their own
            rope_theta=cfg.rope_theta,
            impl=cfg.attention_impl,
            window=cfg.sliding_window if sliding else None,
            qk_norm=True,
            gated=True,
            norm_epsilon=cfg.norm_eps,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="attn",
        )(norm("attn_norm")(x), positions, mask, cache, token_mask)
        if cache is not None:
            attn_out, cache = attn_out
        x = x + norm("post_attn_norm")(attn_out)
        m = norm("mlp_norm")(x)
        if self.index < cfg.n_dense_layers:
            f = swiglu(cfg.hidden_dim, "mlp")(m)
        else:
            with jax.named_scope("afmoe.shared"):
                f = swiglu(cfg.moe_hidden_dim * cfg.n_shared_experts, "shared")(m)
            f = f + ExpertShare(
                n_experts=cfg.n_experts,
                experts_held=cfg.experts_held,
                hidden_dim=cfg.moe_hidden_dim,
                k=cfg.k,
                route_norm=cfg.route_norm,
                route_scale=cfg.route_scale,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                name="moe",
            )(m, token_mask)
        x = x + norm("post_mlp_norm")(f)
        return (x, cache) if cache is not None else x


class AfmoeTransformer(nn.Module):
    """Causal LM: tokens ``[B, L]`` -> logits ``[B, L, vocab]`` (untied head).

    ``token_mask`` (``[B, L]`` bool, False = padding or a finished slot) keeps
    such rows out of the routing and out of every counter. ``counters`` names
    what one call counts into the ``counters`` collection when the caller makes
    it mutable (:class:`~unionml_tpu.models.generate.Generator` does, and hands
    the sums to the serving engine's ``stats()``)."""

    config: AfmoeConfig

    counters = MOE_COUNTERS + ("decode_window_pages_skipped", "kv_positions_attended", "kv_positions_needed")
    counter_views = {"moe": MOE_COUNTERS}  # the serving engine's stats()["moe"]; the attention's counts under their own names

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        positions: Optional[jax.Array] = None,
        return_hidden: bool = False,
        cache: Optional[Tuple[Any, ...]] = None,
        token_mask: Optional[jax.Array] = None,
    ) -> Any:
        cfg = self.config
        x = IotaEmbed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="embed")(tokens)
        if cfg.mup_enabled:
            x = x * jnp.asarray(cfg.dim**0.5, x.dtype)
        if positions is None:
            positions = jnp.arange(tokens.shape[1])
        new_cache = []
        for i in range(cfg.n_layers):
            block = AfmoeBlock(cfg, i, name=f"layer_{i}")
            if cache is not None:
                x, layer_cache = block(x, positions, None, cache[i], token_mask)
                new_cache.append(layer_cache)
            else:
                x = block(x, positions, None, None, token_mask)
        x = RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype, name="final_norm")(x)
        if return_hidden:
            return (x, tuple(new_cache)) if cache is not None else x
        logits = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="lm_head"
        )(x)
        return (logits, tuple(new_cache)) if cache is not None else logits


def afmoe_partition_rules() -> PartitionRules:
    """The held experts' stacked weights shard their leading dim over ``expert``
    (and megatron-style inside an expert); attention (its output gate with the
    query projection), the dense and shared SwiGLUs, embedding and head follow
    the llama layout; the router and every norm replicate."""
    return PartitionRules(
        [
            (r"experts/(wi|wg)/kernel", P("expert", "fsdp", "model")),
            (r"experts/wo/kernel", P("expert", "model", "fsdp")),
            (r"router", P()),
            (r"attn/(q_proj|k_proj|v_proj|gate_proj)/kernel", P("fsdp", "model")),
            (r"attn/o_proj/kernel", P("model", "fsdp")),
            (r"(mlp|shared)/(wi|wg)/kernel", P("fsdp", "model")),
            (r"(mlp|shared)/wo/kernel", P("model", "fsdp")),
            (r"embed/embedding", P("model", "fsdp")),
            (r"lm_head/kernel", P("fsdp", "model")),
            (r".*(norm|scale|bias)", P()),
        ]
    )
