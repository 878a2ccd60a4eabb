"""The glm4_moe_lite decoder (GLM-4.7-Flash; the DeepSeek-V2/V3 block): latent
attention on every layer, two norms a block, and — after the leading dense
layers — a shared expert beside sigmoid-routed experts of which this chip may
hold a share.

Follows :class:`~unionml_tpu.models.llama.Llama`'s cache contract, so
:class:`~unionml_tpu.models.generate.Generator` and the serving engines drive it
unchanged — except that a layer's state is not keys and values: the
configuration states its ``cache_layout``, one latent plane a layer
(:class:`~unionml_tpu.models.layers.LatentAttention`), and
:func:`~unionml_tpu.models.generate.init_cache` /
:func:`~unionml_tpu.models.generate.init_paged_cache` build from it. One block,
with ``h`` the residual stream::

    h = h + mla(attn_norm(h))
    h = h + ffn(mlp_norm(h))

where ``ffn`` is a SwiGLU :class:`~unionml_tpu.models.layers.MLP` on the first
``n_dense_layers`` layers and ``shared(m) + moe(m)`` after them: a shared SwiGLU
expert every token takes, plus :class:`~unionml_tpu.models.moe.ExpertShare`
(sigmoid scores, a selection-only bias, the chosen scores normalised and scaled:
``noaux_tc`` with one group).

**The share.** As :mod:`unionml_tpu.models.afmoe`: ``n_experts`` is the
router's width, ``experts_held = (first, count)`` the experts whose weights this
module holds; the routed sum runs over the held experts only. The multi-token
prediction block (``num_nextn_predict_layers``) is not built: the base model's
forward does not run it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from unionml_tpu.models.layers import MLP, IotaEmbed, LatentAttention, RMSNorm, latent_cache_width
from unionml_tpu.models.moe import MOE_COUNTERS, ExpertShare
from unionml_tpu.parallel.sharding import PartitionRules

LATENT_COUNTERS = ("latent_positions_read", "latent_positions_attended", "latent_positions_needed")


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig:
    vocab_size: int = 154880
    dim: int = 2048
    n_layers: int = 47
    n_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    hidden_dim: int = 10240  # the leading dense layers' SwiGLU width
    moe_hidden_dim: int = 1536  # one expert's (routed or shared) SwiGLU width
    n_experts: int = 64  # the router's width: every routed expert of the model
    experts_held: Optional[Tuple[int, int]] = None  # (first, count) held here; None: all
    k: int = 4
    n_shared_experts: int = 1
    n_dense_layers: int = 1
    route_norm: bool = True
    route_scale: float = 1.8
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 202752
    attention_impl: str = "auto"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self) -> None:
        held = (0, self.n_experts) if self.experts_held is None else tuple(int(v) for v in self.experts_held)
        object.__setattr__(self, "experts_held", held)

    @property
    def cache_layout(self) -> Dict[str, Tuple[int, int]]:
        """A layer's state, planes by name as ``(heads, width)``: one latent row a token, stored
        in whole lanes (576 -> 640, the tail zeros), and no ``"v"``."""
        return {"k": (1, latent_cache_width(self.kv_lora_rank, self.qk_rope_head_dim))}

    @classmethod
    def tiny(cls, **overrides: Any) -> "Glm4MoeLiteConfig":
        """Test scale: one dense layer, then three expert layers."""
        defaults = dict(
            vocab_size=256, dim=64, n_layers=4, n_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
            qk_rope_head_dim=4, v_head_dim=16, hidden_dim=128, moe_hidden_dim=32, n_experts=8, k=2, max_seq_len=128,
        )
        defaults.update(overrides)
        return cls(**defaults)


class Glm4MoeLiteBlock(nn.Module):
    """One decoder block (module docstring); ``index`` picks its feed-forward."""

    config: Glm4MoeLiteConfig
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
        norm = lambda name: RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)  # noqa: E731
        swiglu = lambda width, name: MLP(hidden_dim=width, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)  # noqa: E731
        attn_out = LatentAttention(
            n_heads=cfg.n_heads,
            q_rank=cfg.q_lora_rank,
            kv_rank=cfg.kv_lora_rank,
            nope_dim=cfg.qk_nope_head_dim,
            rope_dim=cfg.qk_rope_head_dim,
            v_dim=cfg.v_head_dim,
            rope_theta=cfg.rope_theta,
            norm_epsilon=cfg.norm_eps,
            impl=cfg.attention_impl,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="attn",
        )(norm("attn_norm")(x), positions, mask, cache, token_mask)
        if cache is not None:
            attn_out, cache = attn_out
        x = x + attn_out
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
        x = x + f
        return (x, cache) if cache is not None else x


class Glm4MoeLiteTransformer(nn.Module):
    """Causal LM: tokens ``[B, L]`` -> logits ``[B, L, vocab]`` (untied head).
    ``token_mask`` and ``counters`` as :class:`~unionml_tpu.models.afmoe.AfmoeTransformer`'s."""

    config: Glm4MoeLiteConfig

    counters = MOE_COUNTERS + LATENT_COUNTERS
    counter_views = {"moe": MOE_COUNTERS, "latent": LATENT_COUNTERS}  # the serving engine's stats()["moe"], ["latent"]
    block = Glm4MoeLiteBlock  # a decoder of the same shell with another block names it here (models/bailing_hybrid.py)

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
        if positions is None:
            positions = jnp.arange(tokens.shape[1])
        new_cache = []
        for i in range(cfg.n_layers):
            block = self.block(cfg, i, name=f"layer_{i}")
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


def glm4_moe_lite_partition_rules() -> PartitionRules:
    """The heads of ``q_up``, ``kv_up`` (columns) and ``o_proj`` (rows) shard over
    ``model``; the two down-projections are shared by every head and follow the
    fsdp axis alone, so the latent they produce — and the latent plane of the
    cache, one head — replicates over ``model``. Experts, SwiGLUs, embedding and
    head follow the afmoe layout; the router and every norm replicate."""
    return PartitionRules(
        [
            (r"experts/(wi|wg)/kernel", P("expert", "fsdp", "model")),
            (r"experts/wo/kernel", P("expert", "model", "fsdp")),
            (r"router", P()),
            (r"attn/(q_up|kv_up)/kernel", P("fsdp", "model")),
            (r"attn/(q_down|kv_down)/kernel", P("fsdp", None)),
            (r"attn/o_proj/kernel", P("model", "fsdp")),
            (r"(mlp|shared)/(wi|wg)/kernel", P("fsdp", "model")),
            (r"(mlp|shared)/wo/kernel", P("model", "fsdp")),
            (r"embed/embedding", P("model", "fsdp")),
            (r"lm_head/kernel", P("fsdp", "model")),
            (r".*(norm|scale|bias)", P()),
        ]
    )
