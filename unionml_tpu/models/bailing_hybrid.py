"""The bailing_hybrid decoder (Ling-3.0-flash): Kimi Delta Attention (KDA) layers whose
state is one matrix a head, whatever the sequence's length, beside latent-attention
(MLA) layers that keep one latent row a token, in a fixed pattern (published: every
sixth layer MLA); two norms a block; after the leading dense layers a shared expert
beside sigmoid-routed experts chosen inside the best routing groups, of which this
chip may hold a share.

Follows :class:`~unionml_tpu.models.llama.Llama`'s cache contract, so
:class:`~unionml_tpu.models.generate.Generator` and the serving engine drive it
unchanged — except that its layers keep two kinds of state, which the
configuration's ``cache_layout`` states **a layer**: a KDA layer two planes with no
position axis (:class:`~unionml_tpu.models.layers.SlotPlane`: the state ``S`` in
float32, the convolutions' tails), an MLA layer one latent plane paged by position
(:class:`~unionml_tpu.models.layers.LatentAttention`). One block, with ``h`` the
residual stream::

    h = h + mix(attn_norm(h))        # mix: KimiDeltaAttention or LatentAttention, by layer_types[i]
    h = h + ffn(mlp_norm(h))

``ffn`` is a SwiGLU :class:`~unionml_tpu.models.layers.MLP` on the first
``n_dense_layers`` layers and ``shared(m) + moe(m)`` after them
(:class:`~unionml_tpu.models.moe.ExpertShare`: sigmoid scores, a selection-only
bias, ``topk_group`` of ``n_group`` groups by the sum of their two best, the chosen
scores normalised and scaled: ``noaux_tc``). The MLA layers have no query
bottleneck, rotate, and gate each head's output by a sigmoid.

**The share** is :mod:`unionml_tpu.models.glm4_moe_lite`'s: ``n_experts`` the
router's width, ``experts_held = (first, count)`` the experts held here. The
multi-token-prediction block (``num_nextn_predict_layers``) is not built (a
rejected draft would have to roll the recurrent state back), nor the clamped
SwiGLU of the published late layers (ROADMAP).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from unionml_tpu.models.glm4_moe_lite import LATENT_COUNTERS, Glm4MoeLiteTransformer
from unionml_tpu.models.layers import (
    MLP,
    KimiDeltaAttention,
    LatentAttention,
    RMSNorm,
    latent_cache_width,
)
from unionml_tpu.models.moe import MOE_COUNTERS, ExpertShare
from unionml_tpu.parallel.sharding import PartitionRules

STATE_COUNTERS = ("state_rows_updated", "state_positions_run", "state_positions_needed")


@dataclasses.dataclass(frozen=True)
class BailingHybridConfig:
    vocab_size: int = 157184
    dim: int = 2560
    n_layers: int = 42
    n_heads: int = 32
    #: each layer's kind, ``"kda"`` or ``"mla"``; None: the published pattern, layer ``i`` MLA iff
    #: ``(i + 1) % layer_group_size == 0``. A cut states the kept layers' kinds so that it keeps the published order
    layer_types: Optional[Tuple[str, ...]] = None
    layer_group_size: int = 6
    kda_head_dim: int = 128
    conv_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    hidden_dim: int = 6144  # the leading dense layers' SwiGLU width
    moe_hidden_dim: int = 768  # one expert's (routed or shared) SwiGLU width
    n_experts: int = 512  # the router's width: every routed expert of the model
    experts_held: Optional[Tuple[int, int]] = None  # (first, count) held here; None: all
    k: int = 8
    n_group: int = 8
    topk_group: int = 4
    n_shared_experts: int = 1
    n_dense_layers: int = 2
    route_norm: bool = True
    route_scale: float = 2.5
    rope_theta: float = 6000000.0
    norm_eps: float = 1e-6
    max_seq_len: int = 262144
    attention_impl: str = "auto"
    state_dtype: Any = jnp.float32  # the recurrent state's; anything lower is a control, not a deployment
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self) -> None:
        held = (0, self.n_experts) if self.experts_held is None else tuple(int(v) for v in self.experts_held)
        object.__setattr__(self, "experts_held", held)
        kinds = self.layer_types
        if kinds is None:
            kinds = tuple("mla" if (i + 1) % self.layer_group_size == 0 else "kda" for i in range(self.n_layers))
        kinds = tuple(kinds)
        if len(kinds) != self.n_layers or set(kinds) - {"kda", "mla"}:
            raise ValueError(f"layer_types must name {self.n_layers} layers 'kda' or 'mla', got {kinds}")
        object.__setattr__(self, "layer_types", kinds)

    @property
    def cache_layout(self) -> Tuple[Dict[str, Any], ...]:
        """Each layer's state: a KDA layer's two slot planes, an MLA layer's one latent plane
        (``(heads, width)``, stored in whole lanes as :class:`Glm4MoeLiteConfig`'s)."""
        kda = KimiDeltaAttention.cache_planes(self.n_heads, self.kda_head_dim, self.conv_size, self.state_dtype, self.dtype)
        mla = {"k": (1, latent_cache_width(self.kv_lora_rank, self.qk_rope_head_dim))}
        return tuple(dict(kda if kind == "kda" else mla) for kind in self.layer_types)

    @classmethod
    def tiny(cls, **overrides: Any) -> "BailingHybridConfig":
        """Test scale: one dense layer, then one period of expert layers (KDA, KDA, MLA, KDA with the dense one)."""
        defaults = dict(
            vocab_size=256, dim=64, n_layers=4, n_heads=4, layer_group_size=3, kda_head_dim=16, kv_lora_rank=16,
            qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, hidden_dim=128, moe_hidden_dim=32, n_experts=8,
            k=2, n_group=4, topk_group=2, n_dense_layers=1, max_seq_len=128,
        )
        defaults.update(overrides)
        return cls(**defaults)


class BailingHybridBlock(nn.Module):
    """One decoder block (module docstring); ``index`` picks its mixer and its feed-forward."""

    config: BailingHybridConfig
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
        if cfg.layer_types[self.index] == "kda":
            mixer = KimiDeltaAttention(
                n_heads=cfg.n_heads,
                head_dim=cfg.kda_head_dim,
                conv_size=cfg.conv_size,
                decay_bound=cfg.kda_lower_bound,
                norm_epsilon=cfg.norm_eps,
                state_dtype=cfg.state_dtype,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                name="attn",
            )
        else:
            mixer = LatentAttention(
                n_heads=cfg.n_heads,
                q_rank=None,
                kv_rank=cfg.kv_lora_rank,
                nope_dim=cfg.qk_nope_head_dim,
                rope_dim=cfg.qk_rope_head_dim,
                v_dim=cfg.v_head_dim,
                rope_theta=cfg.rope_theta,
                norm_epsilon=cfg.norm_eps,
                impl=cfg.attention_impl,
                gated=True,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                name="attn",
            )
        attn_out = mixer(norm("attn_norm")(x), positions, mask, cache, token_mask)
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
                n_group=cfg.n_group,
                topk_group=cfg.topk_group,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                name="moe",
            )(m, token_mask)
        x = x + f
        return (x, cache) if cache is not None else x


class BailingHybridTransformer(Glm4MoeLiteTransformer):
    """Causal LM: tokens ``[B, L]`` -> logits ``[B, L, vocab]`` (untied head):
    :class:`~unionml_tpu.models.glm4_moe_lite.Glm4MoeLiteTransformer`'s shell (embedding, blocks, final norm,
    head, ``token_mask`` and ``counters``) around :class:`BailingHybridBlock`."""

    config: BailingHybridConfig

    counters = MOE_COUNTERS + LATENT_COUNTERS + STATE_COUNTERS
    #: the serving engine's stats()["moe"], ["latent"], ["state"]
    counter_views = {"moe": MOE_COUNTERS, "latent": LATENT_COUNTERS, "state": STATE_COUNTERS}
    block = BailingHybridBlock


def bailing_hybrid_partition_rules() -> PartitionRules:
    """A KDA layer's heads shard over ``model``: ``q_proj``, ``k_proj``, ``v_proj``, ``f_proj``,
    ``g_proj`` by columns, ``o_proj`` by rows, the convolutions' taps and ``dt_bias`` by channel
    (so the state and the tails, whose planes name their heads' axis, shard with them);
    ``b_proj`` (one column a head), ``A_log`` and the head norm replicate. An MLA layer's
    ``q_proj`` and ``kv_up`` (columns) and ``o_proj`` (rows) shard their heads, ``kv_down`` and
    the latent plane replicate over ``model``, and the head-wise gate replicates. The rest
    follows :func:`~unionml_tpu.models.glm4_moe_lite.glm4_moe_lite_partition_rules`."""
    return PartitionRules(
        [
            (r"experts/(wi|wg)/kernel", P("expert", "fsdp", "model")),
            (r"experts/wo/kernel", P("expert", "model", "fsdp")),
            (r"router", P()),
            (r"attn/(q_proj|k_proj|v_proj|f_proj|g_proj|kv_up)/kernel", P("fsdp", "model")),
            (r"attn/(kv_down|b_proj|gate_proj)/kernel", P("fsdp", None)),
            (r"attn/o_proj/kernel", P("model", "fsdp")),
            (r"attn/conv_taps", P(None, None, "model")),
            (r"attn/dt_bias", P("model")),
            (r"(mlp|shared)/(wi|wg)/kernel", P("fsdp", "model")),
            (r"(mlp|shared)/wo/kernel", P("model", "fsdp")),
            (r"embed/embedding", P("model", "fsdp")),
            (r"lm_head/kernel", P("fsdp", "model")),
            (r".*(norm|scale|bias|A_log)", P()),
        ]
    )
