"""Mixture-of-Experts layer + decoder with expert parallelism over an ``expert`` axis.

The reference has no MoE (SURVEY.md §2.3 lists EP as absent; the mesh keeps an
``expert`` axis open per the build plan). The TPU-native design is the
Switch/Mixtral dense-dispatch formulation rather than per-rank alltoall calls:

- the router's top-k choice becomes one-hot **dispatch/combine tensors**, and
  token->expert movement is two einsums — large, static-shape matmuls the MXU
  likes, with no data-dependent control flow under ``jit``;
- expert FFN weights are stacked on a leading ``[n_experts, ...]`` dim and sharded
  ``P("expert", ...)``; the dispatched activations are sharding-constrained to
  ``P("expert", ...)`` on their expert dim, so **XLA emits the all-to-all** from the
  sharding propagation — the compiler-emitted analog of NCCL alltoall in GPU MoE
  stacks;
- each expert processes a fixed ``capacity`` of tokens (static shapes); overflow
  tokens are dropped by the dispatch mask and pass through the residual, the
  standard TPU-friendly trade (capacity_factor controls the drop rate).

Load balancing uses the Switch aux loss (fraction-of-tokens x mean-router-prob per
expert, scaled by n_experts); the layer ``sow``s it under the ``"losses"``
collection and :func:`moe_lm_loss` adds it to the LM loss.

:class:`ExpertShare` is the other formulation, for serving wide models: a
DROPLESS layer that is told which experts it holds. It routes over every expert
of the model, sorts the token-expert pairs that fall on held experts by expert
and runs one grouped matrix product per projection over them; what the absent
experts would have added is left out (their chips add it, in a deployment).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from unionml_tpu.models.layers import MLP, Attention, IotaEmbed, RMSNorm, _Kernel
from unionml_tpu.parallel.sharding import PartitionRules

Dtype = Any


def top_k_dispatch(
    router_probs: jax.Array, k: int, capacity: int, valid: Optional[jax.Array] = None
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Build dispatch/combine tensors from router probabilities.

    :param router_probs: ``[n_tokens, n_experts]`` softmax outputs.
    :param valid: optional ``[n_tokens]`` bool — False tokens (padding) claim no
        expert capacity, get zero dispatch/combine rows, and are excluded from the
        aux loss. Without it, identical pad embeddings all route to the same
        experts and can crowd real tokens out of capacity.
    :returns: ``(dispatch [N, E, C] bool-ish, combine [N, E, C], aux_loss scalar)``.
    """
    n_tokens, n_experts = router_probs.shape
    gate_vals, gate_idx = jax.lax.top_k(router_probs, k)  # [N, k]
    # Mixtral-style renormalization: the k selected gates sum to 1 per token
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    dispatch = jnp.zeros((n_tokens, n_experts, capacity), router_probs.dtype)
    combine = jnp.zeros((n_tokens, n_experts, capacity), router_probs.dtype)
    counts = jnp.zeros((n_experts,), jnp.int32)
    for slot in range(k):  # k is small and static; unrolled at trace time
        onehot = jax.nn.one_hot(gate_idx[:, slot], n_experts, dtype=jnp.int32)  # [N, E]
        if valid is not None:
            onehot = onehot * valid.astype(jnp.int32)[:, None]
        # position of each token within its chosen expert's capacity buffer
        pos = jnp.cumsum(onehot, axis=0) - 1 + counts[None, :]
        counts = counts + onehot.sum(axis=0)
        within = (pos < capacity) & (onehot > 0)
        pos_oh = jax.nn.one_hot(pos, capacity, dtype=router_probs.dtype)  # [N, E, C]
        slot_dispatch = pos_oh * within.astype(router_probs.dtype)[..., None]
        dispatch = dispatch + slot_dispatch
        combine = combine + gate_vals[:, slot, None, None] * slot_dispatch

    # Switch load-balance loss: n_experts * sum_e f_e * p_e, minimized at uniform
    top1 = jax.nn.one_hot(gate_idx[:, 0], n_experts)
    if valid is None:
        token_frac = top1.mean(axis=0)
        prob_frac = router_probs.mean(axis=0)
    else:
        w = valid.astype(router_probs.dtype)[:, None]
        denom = jnp.maximum(w.sum(), 1.0)
        token_frac = (top1 * w).sum(axis=0) / denom
        prob_frac = (router_probs * w).sum(axis=0) / denom
    aux_loss = n_experts * jnp.sum(token_frac * prob_frac)
    return dispatch, combine, aux_loss


class MoELayer(nn.Module):
    """Top-k routed expert FFNs replacing a dense MLP.

    Expert weights live under ``experts/...`` with a leading ``[n_experts]`` dim
    (``nn.vmap``); shard them ``P("expert", ...)`` via :func:`moe_partition_rules`.
    """

    n_experts: int
    hidden_dim: int
    k: int = 2
    capacity_factor: float = 1.25
    gated: bool = True
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, token_mask: Optional[jax.Array] = None) -> jax.Array:
        batch, length, dim = x.shape
        n_tokens = batch * length
        tokens = x.reshape(n_tokens, dim)
        capacity = max(1, int(self.capacity_factor * self.k * n_tokens / self.n_experts))

        # router runs in f32: routing decisions are precision-sensitive
        router_logits = nn.Dense(
            self.n_experts, use_bias=False, dtype=jnp.float32, param_dtype=self.param_dtype, name="router"
        )(tokens.astype(jnp.float32))
        valid = token_mask.reshape(n_tokens) if token_mask is not None else None
        dispatch, combine, aux_loss = top_k_dispatch(
            jax.nn.softmax(router_logits, -1), self.k, capacity, valid
        )
        self.sow("losses", "moe_aux_loss", aux_loss)

        # dispatch: one einsum, [E, C, D] sharded over the expert axis -> XLA
        # inserts the all-to-all between the data-sharded and expert-sharded layouts
        expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(self.dtype), tokens.astype(self.dtype))
        expert_in = _constrain(expert_in, P("expert", None, None))

        experts = nn.vmap(
            MLP,
            in_axes=0,
            out_axes=0,
            variable_axes={"params": 0},
            split_rngs={"params": True},
        )(
            hidden_dim=self.hidden_dim,
            gated=self.gated,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="experts",
        )
        expert_out = experts(expert_in)  # [E, C, D]
        expert_out = _constrain(expert_out, P("expert", None, None))

        out = jnp.einsum("nec,ecd->nd", combine.astype(self.dtype), expert_out)
        return out.reshape(batch, length, dim)


#: what one :class:`ExpertShare` call counts (``counters`` collection; per call, over the rows live in ``token_mask``)
MOE_COUNTERS = ("routed_pairs", "local_pairs", "experts_hit", "max_expert_load")


def route_top_k(
    scores: jax.Array, bias: jax.Array, k: int, *, normalize: bool = True, scale: float = 1.0,
    n_group: int = 1, topk_group: int = 1,
) -> Tuple[jax.Array, jax.Array]:
    """Choose ``k`` experts a token by ``scores + bias`` and weigh them by the
    scores alone (the bias steers the choice, never the mixture): ``(experts [N,
    k] int32, weights [N, k] f32)``. ``normalize`` divides by the chosen scores'
    sum — over all ``k``, wherever their experts live — before ``scale``.

    ``n_group > 1`` limits the choice to groups (DeepSeek-V3's ``noaux_tc``): the
    experts lie in ``n_group`` equal runs, a run scores the sum of its two largest
    ``scores + bias``, the ``topk_group`` best runs stay and every expert of the
    others is out of the choice. One group is the rule above, bit for bit."""
    biased = scores + bias
    if n_group > 1:
        with jax.named_scope("moe.groups"):
            runs = biased.reshape(biased.shape[0], n_group, -1)
            _, best = jax.lax.top_k(jnp.sum(jax.lax.top_k(runs, 2)[0], axis=-1), topk_group)  # [N, topk_group]
            stays = jnp.any(best[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)  # [N, n_group]
            biased = jnp.where(stays[:, :, None], runs, -jnp.inf).reshape(biased.shape)
    _, chosen = jax.lax.top_k(biased, k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), weights * scale


def grouped_matmul(rows: jax.Array, kernels: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``rows [M, K]`` sorted by group, ``kernels [G, K, N]``, ``group_sizes [G]``:
    each group's rows times its own kernel, ``[M, N]``. Rows past the groups'
    total belong to no group: what comes back for them is unspecified (callers
    mask them).

    On a TPU this is the pallas grouped matmul that ships with JAX
    (``jax.experimental.pallas.ops.tpu.megablox.gmm``, ``gmm.<n>`` in a device
    trace): it walks the row tiles each non-empty group touches and streams that
    group's kernel once, so a step reads the weights of the experts that were
    hit and of no other. Elsewhere ``jax.lax.ragged_dot``, which every backend
    has. Measured on a v5e at 32 held experts of 3072 x 3072, all three SwiGLU
    products (PERF.md section 6, "PR 26"): 640 rows of which 67 held (a decode
    step of 160 slots) 2.84 ms against ragged_dot's 3.32; 1,024 rows of which
    124 held (a prefill chunk of 256) 3.22 against 6.51 — ragged_dot, itself a
    Mosaic kernel there, pays for the rows that belong to no group."""
    group_sizes = group_sizes.astype(jnp.int32)
    if jax.default_backend() != "tpu":
        return jax.lax.ragged_dot(rows, kernels, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m, tile = rows.shape[0], 128  # the kernel tiles the rows exactly
    padded = jnp.pad(rows, ((0, -m % tile), (0, 0)))
    # 1024-wide tiles: of those tried (512 .. 3072) none was more than 3 % faster, and these fit any width's VMEM
    out = gmm(padded, kernels, group_sizes, preferred_element_type=rows.dtype, tiling=(tile, 1024, 1024))
    return out[:m]


class _HeldExperts(nn.Module):
    """The held experts' stacked SwiGLU weights: ``wg``, ``wi`` ``[count, D, F]``, ``wo`` ``[count, F, D]``."""

    count: int
    dim: int
    hidden_dim: int
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self) -> Tuple[jax.Array, jax.Array, jax.Array]:
        up, down = (self.count, self.dim, self.hidden_dim), (self.count, self.hidden_dim, self.dim)
        return tuple(
            _Kernel(shape, self.param_dtype, name=name)() for name, shape in (("wg", up), ("wi", up), ("wo", down))
        )


class ExpertShare(nn.Module):
    """One chip's share of a routed-experts feed-forward layer, dropless.

    The router (``router/kernel [D, n_experts]``, float32 compute, sigmoid scores, and the
    selection-only ``router_bias``) scores all ``n_experts``; ``experts_held =
    (first, count)`` says which of them live here, as stacked SwiGLU weights
    under ``experts/{wg,wi,wo}/kernel`` with a leading ``[count]`` dim. Every token
    chooses ``k`` experts; the pairs whose expert is held are sorted by expert,
    pass through one grouped product per projection (no capacity, no ``[N, E,
    C]`` tensor, nothing dropped however skewed the routing), are un-sorted and
    summed under their routing weights. Pairs on experts held elsewhere add
    nothing here, and no code stands in for them. Rows masked out by
    ``token_mask`` (padding, finished slots) route nowhere and count nowhere.

    Counts :data:`MOE_COUNTERS` into the ``counters`` collection when the
    caller makes it mutable.
    """

    n_experts: int
    experts_held: Tuple[int, int]
    hidden_dim: int
    k: int = 2
    route_norm: bool = True
    route_scale: float = 1.0
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    n_group: int = 1  # group-limited routing (route_top_k); one group: none
    topk_group: int = 1

    @nn.compact
    def __call__(self, x: jax.Array, token_mask: Optional[jax.Array] = None) -> jax.Array:
        batch, length, dim = x.shape
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} lies outside the router's {self.n_experts} experts")
        n = batch * length
        tokens = x.reshape(n, dim)
        live = jnp.ones((n,), bool) if token_mask is None else token_mask.reshape(n)

        with jax.named_scope("afmoe.router"):
            router = _Kernel((dim, self.n_experts), self.param_dtype, name="router")()
            bias = self.param("router_bias", nn.initializers.zeros, (self.n_experts,), jnp.float32)
            # float32 at full precision: the choice is discrete, a rounded score flips it
            logits = jnp.dot(
                tokens.astype(jnp.float32), router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
            )
            chosen, weights = route_top_k(
                jax.nn.sigmoid(logits), bias, self.k, normalize=self.route_norm, scale=self.route_scale,
                n_group=self.n_group, topk_group=self.topk_group,
            )
            held = (chosen >= first) & (chosen < first + count) & live[:, None]  # [N, k]
            # pairs on absent experts (and masked rows) sort behind every held expert
            group = jnp.where(held, chosen - first, count).reshape(n * self.k)
            order = jnp.argsort(group, stable=True)
            sizes = jnp.zeros((count + 1,), jnp.int32).at[group].add(1)[:count]
            n_local = jnp.sum(sizes)

        wg, wi, wo = _HeldExperts(count, dim, self.hidden_dim, self.param_dtype, name="experts")()
        with jax.named_scope("afmoe.experts"):
            rows = tokens.astype(self.dtype)[order // self.k]  # [N * k, D], held pairs first, by expert
            gate = jax.nn.silu(grouped_matmul(rows, wg.astype(self.dtype), sizes))
            up = grouped_matmul(rows, wi.astype(self.dtype), sizes)
            out = grouped_matmul(gate * up, wo.astype(self.dtype), sizes)
            weight = jnp.where(held, weights, 0.0).reshape(n * self.k)[order]
            out = jnp.where((jnp.arange(n * self.k) < n_local)[:, None], out.astype(jnp.float32), 0.0) * weight[:, None]
            # un-sort: row i of the pair list is token i // k; sum a token's k pairs
            back = jnp.zeros((n * self.k,), jnp.int32).at[order].set(jnp.arange(n * self.k, dtype=jnp.int32))
            out = jnp.sum(out[back].reshape(n, self.k, dim), axis=1)

        self.sow("counters", "routed_pairs", jnp.sum(live, dtype=jnp.int32) * self.k)
        self.sow("counters", "local_pairs", n_local)
        self.sow("counters", "experts_hit", jnp.sum(sizes > 0, dtype=jnp.int32))
        self.sow("counters", "max_expert_load", jnp.max(sizes))
        return out.astype(self.dtype).reshape(batch, length, dim)


def _constrain(x: jax.Array, spec: P) -> jax.Array:
    """Apply a sharding constraint when running under a mesh that has the axes."""
    names = set()
    for entry in spec:
        if entry is not None:
            names.update(entry if isinstance(entry, tuple) else (entry,))
    # once a mesh with the right axes is found, constraint errors must surface
    # (a swallowed error here silently turns expert parallelism into replication)
    abstract = jax.sharding.get_abstract_mesh()  # set by jax.sharding.use_mesh
    if not abstract.empty:
        if not names.issubset(abstract.axis_names):
            return x
        return jax.lax.with_sharding_constraint(x, spec)
    # `with mesh:` (Mesh context manager) sets only the physical mesh
    from jax._src.mesh import thread_resources

    mesh = thread_resources.env.physical_mesh
    if mesh.empty or not names.issubset(mesh.axis_names):
        return x
    return jax.lax.with_sharding_constraint(x, jax.sharding.NamedSharding(mesh, spec))


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 8
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden_dim: int = 14336
    n_experts: int = 8
    k: int = 2
    capacity_factor: float = 1.25
    moe_every: int = 2  # every Nth block uses MoE FFN (1 = all blocks, Mixtral-style)
    max_seq_len: int = 4096
    rope_theta: float = 500000.0
    aux_loss_weight: float = 0.01
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @classmethod
    def tiny(cls, **overrides: Any) -> "MoEConfig":
        defaults = dict(
            vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
            n_experts=4, k=2, moe_every=1, max_seq_len=128,
        )
        defaults.update(overrides)
        return cls(**defaults)


class MoEBlock(nn.Module):
    """Pre-norm decoder block with a routed-experts FFN."""

    config: MoEConfig

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
        attn_out = Attention(
            n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads,
            causal=True,
            rope=True,
            rope_theta=cfg.rope_theta,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="attn",
        )(RMSNorm(dtype=cfg.dtype, name="attn_norm")(x), positions, mask, cache)
        if cache is not None:
            attn_out, cache = attn_out
        x = x + attn_out
        x = x + MoELayer(
            n_experts=cfg.n_experts,
            hidden_dim=cfg.hidden_dim,
            k=cfg.k,
            capacity_factor=cfg.capacity_factor,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="moe",
        )(RMSNorm(dtype=cfg.dtype, name="moe_norm")(x), token_mask)
        return (x, cache) if cache is not None else x


class MoETransformer(nn.Module):
    """Causal LM with routed-expert FFNs (Mixtral-family shape): tokens -> logits.

    Follows the same cache contract as :class:`~unionml_tpu.models.llama.Llama`, so
    :class:`~unionml_tpu.models.generate.Generator` serves it unchanged.
    ``token_mask`` (``[B, L]`` bool, False = padding) keeps pad tokens from
    claiming expert capacity — without it, bucketed/batch-padded serving would
    let identical pad embeddings crowd real tokens out of their experts.
    Capacity under incremental decoding is per routed group (per decode step);
    size ``capacity_factor`` for the serving batch, not the training sequence.
    """

    config: MoEConfig

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        positions: Optional[jax.Array] = None,
        return_hidden: bool = False,
        cache: Optional[Tuple[Any, ...]] = None,
        token_mask: Optional[jax.Array] = None,
    ) -> Any:
        from unionml_tpu.models.layers import TransformerBlock

        cfg = self.config
        x = IotaEmbed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="embed")(tokens)
        if positions is None:
            positions = jnp.arange(tokens.shape[1])
        new_cache = []
        for i in range(cfg.n_layers):
            moe_block = i % cfg.moe_every == cfg.moe_every - 1
            if moe_block:
                block = MoEBlock(cfg, name=f"layer_{i}")
            else:
                block = TransformerBlock(
                    n_heads=cfg.n_heads,
                    n_kv_heads=cfg.n_kv_heads,
                    hidden_dim=cfg.hidden_dim,
                    decoder=True,
                    rope=True,
                    rope_theta=cfg.rope_theta,
                    dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype,
                    name=f"layer_{i}",
                )
            extra = (token_mask,) if moe_block else ()  # only routed blocks consume it
            if cache is not None:
                x, layer_cache = block(x, positions, None, cache[i], *extra)
                new_cache.append(layer_cache)
            else:
                x = block(x, positions, None, None, *extra)
        x = RMSNorm(dtype=cfg.dtype, name="final_norm")(x)
        if return_hidden:
            return (x, tuple(new_cache)) if cache is not None else x
        logits = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="lm_head"
        )(x)
        return (logits, tuple(new_cache)) if cache is not None else logits


def moe_partition_rules() -> PartitionRules:
    """Expert-parallel layout: stacked expert weights shard their leading dim over
    ``expert``; within an expert the megatron TP pattern applies on the trailing
    dims; everything else follows the llama rules."""
    return PartitionRules(
        [
            (r"experts/(wi|wg)/kernel", P("expert", "fsdp", "model")),
            (r"experts/wo/kernel", P("expert", "model", "fsdp")),
            (r"experts/.*(bias|scale)", P("expert")),
            (r"router/kernel", P()),
            (r"attn/(q_proj|k_proj|v_proj)/kernel", P("fsdp", "model")),
            (r"attn/o_proj/kernel", P("model", "fsdp")),
            # dense interleaved blocks (moe_every > 1) follow the llama MLP layout
            (r"mlp/(wi|wg)/kernel", P("fsdp", "model")),
            (r"mlp/wo/kernel", P("model", "fsdp")),
            (r"embed/embedding", P("model", "fsdp")),
            (r"lm_head/kernel", P("fsdp", "model")),
            (r".*(norm|scale|bias)", P()),
        ]
    )


def moe_lm_loss(module: MoETransformer, params: Any, batch: Any) -> jax.Array:
    """Next-token cross-entropy + weighted router load-balance aux loss.

    ``batch``: tokens array or ``(tokens, loss_mask)`` — same contract as
    :func:`unionml_tpu.models.llama.causal_lm_loss`.
    """
    import optax

    tokens, mask = (batch if isinstance(batch, (tuple, list)) and len(batch) == 2 else (batch, None))
    if isinstance(tokens, (tuple, list)):
        tokens = tokens[0]
    logits, state = module.apply({"params": params}, tokens, mutable=["losses"])
    targets = tokens[:, 1:]
    losses = optax.softmax_cross_entropy_with_integer_labels(logits[:, :-1].astype(jnp.float32), targets)
    aux_terms = jax.tree_util.tree_leaves(state.get("losses", {}))
    aux = sum(jnp.sum(t) for t in aux_terms) if aux_terms else jnp.float32(0.0)
    if mask is not None:
        m = mask[:, 1:].astype(jnp.float32)
        ce = (losses * m).sum() / jnp.maximum(m.sum(), 1.0)
    else:
        ce = losses.mean()
    return ce + module.config.aux_loss_weight * aux
