"""Autoregressive generation engine: bucketed jitted prefill + one-compile decode loop.

The reference has no inference engine at all (it serves whatever ``model.predict``
does eagerly, unionml/fastapi.py:50-64); for the LLM family that leaves the flagship
model unservable. This module is the TPU-native answer, built on the same rules as
the serving layer's :class:`~unionml_tpu.serving.compile.CompiledPredictor`:

- **static shapes only**: prompts are padded to configured length buckets, the KV
  cache is a fixed ``[B, S_max, H_kv, D]`` ring of buffers, and the decode loop is a
  ``lax.scan`` over ``max_new_tokens`` steps — XLA sees ``len(buckets)`` prefill
  shapes and exactly one decode shape per (batch, cache_len);
- **per-example contiguous cache rows**: variable-length prompts are right-padded
  and each example's K/V rows are written at its own offsets
  (:func:`~unionml_tpu.models.layers._write_cache`), so no left-padding or position
  remapping is needed and RoPE positions equal cache slots;
- **cache donation**: prefill and every decode dispatch donate the cache buffers,
  so HBM holds one cache, not two;
- **mesh placement**: with a mesh + partition rules the params are placed sharded
  (e.g. megatron TP via :func:`~unionml_tpu.models.llama.llama_partition_rules`) and
  the cache is sharded batch-over-``data`` / heads-over-``model``; XLA inserts the
  collectives, identical tokens come out (tests/emulated/test_generate_tp.py).

Works with any flax module following the :class:`~unionml_tpu.models.llama.Llama`
cache contract: ``apply(vars, tokens, positions=[B,L], cache=...) -> (out, cache)``
(and ``return_hidden=True`` giving pre-head hidden states so prefill never
materializes a ``[B, P, vocab]`` logits tensor).
"""

from __future__ import annotations

import dataclasses
import math
import threading
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from unionml_tpu._logging import logger
from unionml_tpu.models.layers import SlotPlane

__all__ = [
    "cache_layouts",
    "DraftSpec",
    "GenerationConfig",
    "Generator",
    "PrefixCache",
    "init_cache",
    "init_paged_cache",
    "sample_tokens",
]


@dataclasses.dataclass(frozen=True)
class DraftSpec:
    """A draft model for speculative decoding, attachable to
    :attr:`GenerationConfig.draft`: the :class:`Generator` façade then routes
    ``__call__``/``stream`` through a
    :class:`~unionml_tpu.models.speculative.SpeculativeGenerator` — same output
    law (greedy: token-exact; sampled: distribution-exact), fewer target
    dispatches per token. ``quantize`` ("int8") stores the DRAFT's weights
    quantized too — None follows the serve-wide ``UNIONML_TPU_QUANTIZE``
    default, exactly like the target Generator's own kwarg, so a quantized
    serving fleet drafts in int8 without a second knob. The output law is
    unchanged either way: the draft only proposes, the target decides."""

    module: Any
    params: Any
    gamma: int = 4
    partition_rules: Optional[Any] = None
    quantize: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    """Decoding knobs. ``temperature == 0`` means greedy (argmax) decoding;
    ``top_k``/``top_p`` filter the distribution before sampling."""

    max_new_tokens: int = 128
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    eos_id: Optional[int] = None
    pad_id: int = 0
    #: prompt-length buckets; a batch's prompts are padded to the smallest bucket
    #: that fits, so XLA compiles at most ``len(prompt_buckets)`` prefill shapes
    prompt_buckets: Tuple[int, ...] = (64, 256, 1024)
    #: long-context prefill: process the prompt in fixed chunks of this many
    #: tokens through the cache instead of one [B, bucket] dispatch — activation
    #: memory stays O(B * chunk * dim) and ONE chunk shape covers every prompt
    #: length (the chunk fn compiles once, prompt buckets stop mattering for
    #: compile count). None = single-dispatch prefill.
    prefill_chunk: Optional[int] = None
    #: "int8" stores K/V rows symmetric-quantized per (position, head) with f32
    #: scales — long-context decode streams the cache every step, and int8
    #: halves those bytes (~0.4% logit drift on the shipped models' scale).
    #: None = compute dtype (bf16 on TPU).
    kv_cache_dtype: Optional[str] = None
    #: "ring" / "ulysses": run prefill SEQUENCE-PARALLEL over the mesh's
    #: ``sequence`` axis (the whole decoder under shard_map with the module's
    #: sequence-parallel attention), then assemble the KV cache from the sown
    #: per-layer K/V — prefill of a 100k-token prompt spreads across chips
    #: instead of living on one. Requires a mesh with a ``sequence`` axis;
    #: decode afterwards is the ordinary cached path.
    sp_prefill: Optional[str] = None
    #: attach a :class:`DraftSpec` to decode speculatively through the same
    #: Generator façade (excluded from equality/repr — it carries param trees)
    draft: Optional["DraftSpec"] = dataclasses.field(default=None, compare=False, repr=False)
    #: a :class:`~unionml_tpu.models.structured.ConstraintSet` enabling
    #: grammar-constrained decoding: pass ``constraint=`` (grammar ids) to
    #: :meth:`Generator.__call__` / :meth:`Generator.stream` and each row's
    #: logits are masked by its grammar's token-DFA inside the decode scan.
    #: Excluded from equality/repr — it carries the DFA tables.
    constraints: Optional[Any] = dataclasses.field(default=None, compare=False, repr=False)
    #: keep only tokens whose probability is at least ``min_p`` times the most
    #: likely token's (applied after temperature, before top-k/top-p) — an
    #: adaptive nucleus: permissive when the model is unsure, sharp when it is
    #: confident. 0.0 disables. Appended last so existing positional
    #: construction is unaffected.
    min_p: float = 0.0


def chunk_aligned(length: int, chunk: int) -> int:
    """Round ``length`` up to a multiple of ``chunk`` — the width a chunked
    prefill actually pads to and writes. Every cache sized to receive a chunked
    prefill must use THIS width (not the raw bucket), so the sizing rule lives
    in one place (round 3 had a hand-copied variant drift and clamp-corrupt
    cache rows in continuous batching)."""
    return -(-length // chunk) * chunk


def _head_dim(config: Any) -> int:
    """A head's width: the configuration's ``head_dim`` where it states one (a
    published head width need not be ``dim // n_heads``), else that quotient."""
    return int(getattr(config, "head_dim", None) or config.dim // config.n_heads)


def _stated_layout(stated: Any, dtype: Any, kv_dtype: Optional[str]) -> Dict[str, Any]:
    """One layer's planes as a configuration states them, the paged ones given the compute dtype."""
    if kv_dtype == "int8":
        raise ValueError(
            f"kv_cache_dtype='int8' over a stated cache layout ({dict(stated)}): int8 pages are "
            "per-head keys and values with a scale a (position, head); a latent plane or a "
            "recurrent state a slot has none"
        )
    return {
        name: plane if isinstance(plane, SlotPlane) else (int(plane[0]), int(plane[1]), dtype)
        for name, plane in stated.items()
    }


def cache_layout(config: Any, kv_dtype: Optional[str] = None) -> Dict[str, Tuple[int, int, Any]]:
    """A layer's cache planes by name, each ``(heads, width, dtype)``: what the
    configuration states (``config.cache_layout``: ``{name: (heads, width)}`` in
    the compute dtype — a latent layer's ``{"k": (1, 640)}``, no ``"v"``), else
    keys and values at ``n_kv_heads`` heads of the configuration's own head
    width. ``kv_dtype="int8"`` stores those two int8 beside per-(position, head)
    float32 scale planes; a stated layout has no such form. This is the layout
    of a configuration that means one for every layer; one that states a layout
    a layer is read through :func:`cache_layouts`."""
    if kv_dtype not in (None, "int8"):
        raise ValueError(f"unsupported kv_cache_dtype {kv_dtype!r}; expected None or 'int8'")
    stated = getattr(config, "cache_layout", None)
    if stated:
        if not isinstance(stated, dict):
            raise ValueError("this configuration states a cache layout a layer: read it through cache_layouts")
        return _stated_layout(stated, config.dtype, kv_dtype)
    heads, width = config.n_kv_heads, _head_dim(config)
    if kv_dtype == "int8":
        return {
            "k": (heads, width, jnp.int8), "v": (heads, width, jnp.int8),
            "k_scale": (heads, 1, jnp.float32), "v_scale": (heads, 1, jnp.float32),
        }
    return {"k": (heads, width, config.dtype), "v": (heads, width, config.dtype)}


def cache_layouts(config: Any, kv_dtype: Optional[str] = None) -> Tuple[Dict[str, Any], ...]:
    """Every layer's planes, ``config.n_layers`` layouts. A plane is of one of two
    kinds: paged by position, ``(heads, width, dtype)`` as :func:`cache_layout`
    gives it, or one row a slot, a :class:`~unionml_tpu.models.layers.SlotPlane`
    (``shape``, its own ``dtype``: a recurrent state, a convolution's tail). A
    configuration that states one layout (or none) means it for every layer; one
    whose layers differ states a sequence of them, one a layer."""
    stated = getattr(config, "cache_layout", None)
    if stated and not isinstance(stated, dict):
        if len(stated) != config.n_layers:
            raise ValueError(f"cache_layout states {len(stated)} layers, the model has {config.n_layers}")
        return tuple(_stated_layout(layout, config.dtype, kv_dtype) for layout in stated)
    return (cache_layout(config, kv_dtype),) * config.n_layers


def has_slot_planes(config: Any) -> bool:
    """Whether any layer keeps state with no position axis (a row a slot)."""
    return any(isinstance(plane, SlotPlane) for layout in cache_layouts(config) for plane in layout.values())


def refuse_draft_over_slot_state(config: Any) -> None:
    """Speculative decoding over a model with slot planes: the one refusal of ``Generator``, ``SpeculativeGenerator``
    and, through them, the engine."""
    if has_slot_planes(config):
        raise ValueError(
            "speculative decoding (config.draft) over a model that keeps a recurrent state a slot: "
            "a rejected draft would have to roll the state back, and no snapshot of it is kept"
        )


def _zeros(plane: Any, rows: int, paged_shape: Callable[[int, int], Tuple[int, ...]]) -> jax.Array:
    if isinstance(plane, SlotPlane):
        return jnp.zeros((rows, *plane.shape), plane.dtype)
    heads, width, dtype = plane
    return jnp.zeros(paged_shape(heads, width), dtype)


def init_cache(config: Any, batch: int, cache_len: int, kv_dtype: Optional[str] = None) -> Tuple[Any, ...]:
    """Zeroed per-layer cache buffers ``[batch, cache_len, heads, width]`` for a
    decoder with ``config.n_layers`` layers, one per plane of
    :func:`cache_layouts`: by default ``config.n_kv_heads`` KV heads and the
    configuration's own head width (``head_dim``, else ``dim // n_heads``), stored in the
    compute dtype (bf16 on TPU — halves cache HBM vs f32). ``kv_dtype="int8"``
    adds per-(position, head) scale planes and stores values int8 (see
    :class:`~unionml_tpu.models.layers.Attention`'s cached branch). A slot plane
    is ``[batch, *shape]`` in its own dtype, whatever ``cache_len``."""
    return tuple(
        {name: _zeros(plane, batch, lambda heads, width: (batch, cache_len, heads, width)) for name, plane in layout.items()}
        for layout in cache_layouts(config, kv_dtype)
    )


def init_paged_cache(
    config: Any,
    slots: int,
    n_blocks: int,
    block_size: int,
    max_blocks: int,
    kv_dtype: Optional[str] = None,
    *,
    fill_block: int,
) -> Tuple[Any, ...]:
    """Per-layer PAGED KV buffers: a shared pool of ``n_blocks`` blocks of
    ``block_size`` positions plus a ``[slots, max_blocks]`` block table
    initialized to ``fill_block``. Pools are HEADS-MAJOR
    (``[H_kv, n_blocks, block_size, D]``) — the layout
    ``jax.experimental.pallas.ops.tpu.paged_attention`` consumes directly, so
    the kernel path needs no transpose. The planes are :func:`cache_layouts`'s:
    a latent layer has one, ``{"k": [1, n_blocks, block_size, W], "table"}``; a
    layer whose planes are all slot planes holds ``[slots, *shape]`` each and NO
    table (it owns no block: that is how the engine's programs tell it).
    ``fill_block`` is REQUIRED and must be a reserved scratch block (allocate
    ``n_blocks = real + 1`` and pass ``fill_block = real``, as
    ``ContinuousBatcher._init_carry`` does): free and finished slots keep
    issuing one ride-along K/V write per step through their table row, and a
    default of 0 would scatter that garbage into live block 0. The layer dicts
    follow :func:`init_cache`'s int8 convention, with the table riding in each
    layer (same values; a few hundred bytes). See
    :meth:`unionml_tpu.models.layers.Attention._paged_cached_attention` for the
    read/write contract; HBM scales with the pool, not slots x worst-case."""
    # one table PER layer (same values): the cache is donated through admission
    # and decode, and donating an array aliased across layers is an XLA error
    # ("donate the same buffer twice"); the duplication is a few hundred bytes
    layers = []
    for layout in cache_layouts(config, kv_dtype):
        layer = {name: _zeros(plane, slots, lambda heads, width: (heads, n_blocks, block_size, width)) for name, plane in layout.items()}
        kinds = {isinstance(plane, SlotPlane) for plane in layout.values()}
        if kinds == {True, False}:
            raise ValueError(f"a layer's planes are all paged or all a row a slot, not both: {sorted(layout)}")
        if kinds == {False}:
            layer["table"] = jnp.full((slots, max_blocks), fill_block, jnp.int32)
        layers.append(layer)
    return tuple(layers)


def paste_prefix_rows(cache: Any, prefix_layers: Any) -> Any:
    """Broadcast a :class:`PrefixCache`'s ``[1, p0, ...]`` K/V rows into slots
    ``[0, p0)`` of every row of a freshly allocated cache. ``_paste_prefix_rows``
    is this jitted (donating the cache), so the paste is one fused dispatch, not
    2 * n_layers eager ops; a program that builds its own rows calls this."""

    def paste(buf: jax.Array, pre: jax.Array) -> jax.Array:
        pre = jnp.broadcast_to(pre.astype(buf.dtype), (buf.shape[0],) + pre.shape[1:])
        return jax.lax.dynamic_update_slice(buf, pre, (0,) * buf.ndim)

    return jax.tree_util.tree_map(paste, cache, prefix_layers)


_paste_prefix_rows = jax.jit(paste_prefix_rows, donate_argnums=(0,))


def gather_paged_rows(pool_cache: Any, blocks_row: jax.Array, width: int) -> Tuple[Any, ...]:
    """Materialize a dense ``[1, width, H_kv, last]`` cache row from a PAGED
    pool (:func:`init_paged_cache`): the blocks ``blocks_row`` names are read
    as whole pages (``pool[:, blocks_row]``, the pool's own layout, so no pool
    is re-laid for the read), run together and cut to ``width`` — the exact
    inverse, page-wise, of the admission's page write, so a row gathered from
    cached blocks is bit-identical to the row that was written in. The serving
    engine's radix prefix cache uses this to seed an admission's prefill row
    from arbitrary cached block runs (positions past the cached region gather
    scratch/garbage, which the suffix prefill overwrites before anything can
    attend to it). ``width`` is static (one compile per engine: callers pass
    their fixed ``cache_len``) and at most ``len(blocks_row) * block_size``;
    the per-layer ``table`` entries ride along unused."""
    rows = []
    for layer in pool_cache:
        if "table" not in layer:
            raise ValueError("a layer that keeps a row a slot has no pages to gather: its state at a position is not held")
        row = {}
        for name in layer:
            if name == "table":
                continue
            pages = layer[name][:, blocks_row]  # pools are heads-major [H, NB, bs, last]: [H, n, bs, last]
            run = pages.reshape(pages.shape[0], -1, pages.shape[-1])[:, :width]
            row[name] = jnp.swapaxes(run, 0, 1)[None]  # [1, width, H, last], the dense-row layout
        rows.append(row)
    return tuple(rows)


def _quantized_shardings(qparams: Any, shardings: Any, mesh: Any) -> Any:
    """Expand a (pre-quantization) sharding tree to match a quantized params tree:
    each :class:`~unionml_tpu.ops.quant.QuantizedTensor` leaf becomes a
    QuantizedTensor of shardings — the int8 values take the kernel's resolved
    sharding, and the per-channel ``scale`` keeps only the axes on its non-unit
    dims (size-1 reduction dims cannot carry a mesh axis)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from unionml_tpu.ops.quant import QuantizedTensor

    def fix(leaf: Any, sharding: Any) -> Any:
        if not isinstance(leaf, QuantizedTensor):
            return sharding
        spec = tuple(sharding.spec) + (None,) * (len(leaf.scale.shape) - len(tuple(sharding.spec)))
        scale_spec = tuple(None if dim == 1 else axis for dim, axis in zip(leaf.scale.shape, spec))
        return QuantizedTensor(q=sharding, scale=NamedSharding(mesh, P(*scale_spec)))

    return jax.tree_util.tree_map(
        fix, qparams, shardings, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    )


def filtered_logits(logits: jax.Array, config: GenerationConfig) -> jax.Array:
    """Apply the decoding policy's temperature/top-k/top-p filters to ``[..., V]``
    logits (masked entries become -inf). ``softmax`` of the result IS the policy's
    sampling distribution — speculative sampling rejects against exactly this."""
    logits = logits / config.temperature
    if config.min_p > 0.0:
        # prob(x) >= min_p * prob(argmax)  <=>  logit(x) >= max_logit + log(min_p)
        # (softmax normalizers cancel), so the filter needs no softmax at all
        cutoff = jnp.max(logits, axis=-1, keepdims=True) + jnp.log(config.min_p)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    if config.top_k > 0:
        kth = jnp.sort(logits, axis=-1)[..., -config.top_k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if config.top_p < 1.0:
        sorted_desc = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        exclusive_cum = jnp.cumsum(probs, axis=-1) - probs
        # keep the smallest prefix whose mass reaches top_p; the lowest kept logit
        # becomes the cutoff mapped back onto the unsorted axis
        dropped = exclusive_cum >= config.top_p
        min_kept = jnp.min(jnp.where(dropped, jnp.inf, sorted_desc), axis=-1, keepdims=True)
        logits = jnp.where(logits < min_kept, -jnp.inf, logits)
    return logits


def policy_probs(logits: jax.Array, config: GenerationConfig) -> jax.Array:
    """The decoding policy as an explicit distribution over ``[..., V]`` — a
    one-hot argmax for greedy, else softmax of :func:`filtered_logits`."""
    if config.temperature == 0.0:
        return jax.nn.one_hot(jnp.argmax(logits, axis=-1), logits.shape[-1], dtype=jnp.float32)
    return jax.nn.softmax(filtered_logits(logits.astype(jnp.float32), config), axis=-1)


def sample_tokens(logits: jax.Array, key: jax.Array, config: GenerationConfig) -> jax.Array:
    """Sample next tokens from ``logits [B, V]`` under the config's decoding policy."""
    if config.temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, filtered_logits(logits, config)).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class PrefixCache:
    """Precomputed K/V rows for a shared prompt prefix (a system prompt): built
    once by :meth:`Generator.cache_prefix`, reused by every request that passes
    it — the prefix's prefill cost is paid once, not per call."""

    layers: Tuple[Any, ...]  # per-layer cache leaves trimmed to [1, length, ...]
    length: int
    #: the prefix's token ids — kept so engines with a SECOND model (speculative
    #: decoding's draft) can prefill the same prefix through it; None for
    #: hand-built caches, which then can't compose with a draft
    tokens: Optional[Tuple[int, ...]] = None


class Generator:
    """Batch text generation over a cached decoder.

    >>> gen = Generator(module, params, GenerationConfig(max_new_tokens=64))
    >>> tokens = gen([[1, 5, 9], [3, 3]], seed=0)   # [2, 64] int32

    ``prefill_traces`` / ``decode_traces`` count XLA traces; within the configured
    prompt buckets and a fixed batch size they stay at (<= len(buckets), 1).
    """

    def __init__(
        self,
        module: Any,
        params: Any,
        config: GenerationConfig = GenerationConfig(),
        *,
        mesh: Optional[Any] = None,
        partition_rules: Optional[Any] = None,
        quantize: Optional[str] = None,
    ):
        from unionml_tpu.defaults import serve_kv_cache_dtype, serve_quantize

        # serve-time quantization defaults (the --dp-replicas early-export
        # contract): an unset kwarg falls back to the serve CLI's
        # UNIONML_TPU_QUANTIZE export, and an unset config.kv_cache_dtype to
        # UNIONML_TPU_KV_CACHE_DTYPE — so `serve --quantize int8
        # --kv-cache-dtype int8` quantizes app-built Generators with zero app
        # code changes. Explicit values always win; with the env unset both
        # resolutions are identity and nothing changes.
        if quantize is None:
            quantize = serve_quantize()
        if config.kv_cache_dtype is None:
            env_kv = serve_kv_cache_dtype()
            if env_kv is not None:
                config = dataclasses.replace(config, kv_cache_dtype=env_kv)
        if config.kv_cache_dtype not in (None, "int8"):
            # init_cache would raise the same at first use; failing at
            # construction keeps the error next to the config that caused it
            raise ValueError(
                f"unsupported kv_cache_dtype {config.kv_cache_dtype!r}; expected None or 'int8'"
            )
        if config.draft is not None:
            refuse_draft_over_slot_state(module.config)
        self.module = module
        self.config = config
        self.mesh = mesh
        #: retained so engines re-hosting these weights (the serving replica
        #: layer re-placing params onto per-replica submeshes) can rebuild a
        #: Generator with identical sharding/quantization choices
        self.partition_rules = partition_rules
        self.quantize = quantize
        self.prefill_traces = 0
        self.decode_traces = 0
        #: how the decode program reads a PAGED cache, recorded when it is traced:
        #: ``"paged_kernel"`` or ``"gather"`` (ops/paged_attention.py); ``None``
        #: before the first trace and for a contiguous cache
        self.decode_attention_path: Optional[str] = None
        #: how the decode program updates a recurrent state a slot, recorded when it is traced:
        #: ``"state_kernel"`` or ``"state_xla"`` (ops/delta_rule.py); ``None`` before the first
        #: trace and for a model with no slot state
        self.decode_state_path: Optional[str] = None
        #: how a prefill (a chunk, a whole prompt) reads a row cache too long to read whole in one trip, recorded
        #: when such a program is traced: ``"chunk_kernel"`` or ``"chunk_xla"`` (ops/chunk_attention.py); ``None``
        #: before, and for a model whose rows are all read whole
        self.chunk_attention_path: Optional[str] = None
        #: the newest traced apply's paged read (``read``), state update (``state``) and long-row read (``chunk``), kept
        #: apart by the thread that traced it: a warm-up traces programs on several at once
        self._traced = threading.local()
        #: what the module counts per call (its ``counters`` attribute: names it sows into
        #: the ``counters`` collection; ``max_*`` merge by maximum, the rest add up). With
        #: any, ``prefill_chunk`` returns the chunk's counts as its fourth output and the
        #: decode carry ends in the counts of the dispatch that returned it (``[len(names)]`` int32)
        self.counter_names: Tuple[str, ...] = tuple(getattr(module, "counters", ()))
        #: how the module wants them served (its ``counter_views``: stats key -> the names under it)
        self.counter_views: Dict[str, Tuple[str, ...]] = dict(getattr(module, "counter_views", {}))
        compute_dtype = getattr(getattr(module, "config", None), "dtype", jnp.bfloat16)

        if quantize not in (None, "int8"):
            raise ValueError(f"unsupported quantize mode {quantize!r}; expected None or 'int8'")

        from unionml_tpu.parallel.sharding import combine_fsdp_tp, shard_pytree, unbox_partitioned

        # resolve shardings from the still-boxed tree so nn.Partitioned metadata
        # keeps its precedence over regex rules / inferred FSDP, then unbox (the
        # sharding tree matches the unboxed structure)
        shardings = combine_fsdp_tp(params, mesh, partition_rules) if mesh is not None else None
        params = unbox_partitioned(params)
        if quantize == "int8":
            from unionml_tpu.ops.quant import quantize_params

            params = quantize_params(params)
            if shardings is not None:
                shardings = _quantized_shardings(params, shardings, mesh)
        if shardings is not None:
            params = shard_pytree(params, shardings)
        self.params = params

        if quantize == "int8":
            from unionml_tpu.ops.quant import dequantize_tree

            # called inside jit (and inside the decode scan body): XLA fuses the
            # int8->compute convert into consumers; int8 is what crosses HBM
            dequant = lambda p: dequantize_tree(p, dtype=compute_dtype)  # noqa: E731
        else:
            dequant = lambda p: p  # noqa: E731
        self._dequant_params = dequant  # for engines composing on top (speculative)

        cs = config.constraints
        if cs is not None:
            # the tables ride to the device once and are MEMOIZED on the set —
            # plain/target/draft engines over one ConstraintSet share a single
            # copy; inside the jitted step the constraint is two gathers and a
            # where (see models/structured.py). With config.draft also set, the
            # speculative engine threads the same per-row DFA state along the
            # draft path (speculative.py).
            self._cs_trans, self._cs_allowed = cs.device_tables()
        self._cs = cs

        def constrain(logits: jax.Array, cstate: tuple) -> jax.Array:
            """Mask ``[..., V]`` logits by each row's DFA state (``cstate`` is
            the variadic tail — empty when the generator is unconstrained, so
            every unconstrained signature and carry layout stays exactly as
            before)."""
            if cs is None:
                return logits
            return jnp.where(self._cs_allowed[cstate[0]], logits, -jnp.inf)

        self._constrain = constrain  # shared by sp_prefill and beam search

        from unionml_tpu.ops.chunk_attention import CHUNK_KERNEL, CHUNK_XLA
        from unionml_tpu.ops.delta_rule import STATE_KERNEL, STATE_XLA
        from unionml_tpu.ops.paged_attention import paged_read_scope

        # a paged pool shards its KV heads over the mesh (_place_paged_cache);
        # the layers see tracers, not placements, so the trace is told
        pools_sharded = mesh is not None and mesh.devices.size > 1

        names = self.counter_names
        by_max = np.array([name.startswith("max_") for name in names], bool)

        def merge_counts(a: jax.Array, b: jax.Array) -> jax.Array:
            return jnp.where(by_max, jnp.maximum(a, b), a + b)

        def collect_counts(sown: Any) -> jax.Array:
            """One call's counts, ``[len(names)]`` int32, from what its layers sowed under each name."""
            found: dict = {name: [] for name in names}
            for path, leaf in jax.tree_util.tree_flatten_with_path(sown)[0]:
                name = [k.key for k in path if isinstance(k, jax.tree_util.DictKey)][-1]
                if name in found:
                    found[name].append(leaf.astype(jnp.int32))
            merged = [
                (jnp.max if name.startswith("max_") else jnp.sum)(jnp.stack(found[name])) if found[name] else jnp.int32(0)
                for name in names
            ]
            return jnp.stack(merged) if merged else jnp.zeros((0,), jnp.int32)

        def apply_counted(p: Any, tokens: jax.Array, positions: jax.Array, cache: Any, token_mask: Any):
            with paged_read_scope(sharded=pools_sharded) as paths:
                out = module.apply(
                    {"params": p},
                    tokens,
                    positions=positions,
                    return_hidden=True,
                    cache=cache,
                    token_mask=token_mask,
                    mutable=["counters"] if names else False,
                )
            (hidden, cache), sown = out if names else (out, {})
            # which way a paged cache was read (None: a contiguous one), a slot state updated (None: none is
            # kept) and a long row walked (None: no read walked one), for whoever traces a program
            updates, chunks = set(paths) & {STATE_KERNEL, STATE_XLA}, set(paths) & {CHUNK_KERNEL, CHUNK_XLA}
            self._traced.read = "+".join(sorted(set(paths) - updates - chunks)) or None
            self._traced.state = "+".join(sorted(updates)) or None
            self._traced.chunk = "+".join(sorted(chunks)) or None
            return hidden, cache, collect_counts(sown.get("counters", {}))

        def apply(p: Any, tokens: jax.Array, positions: jax.Array, cache: Any, token_mask: Any):
            return apply_counted(p, tokens, positions, cache, token_mask)[:2]

        def head(p: Any, hidden: jax.Array) -> jax.Array:
            kernel = p["lm_head"]["kernel"]
            return (hidden @ kernel.astype(hidden.dtype)).astype(jnp.float32)

        def prefill(p, tokens, lengths, cache, key, row_valid, *cstate):
            self.prefill_traces += 1
            p = dequant(p)
            batch, prompt_len = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(prompt_len)[None], (batch, prompt_len))
            # padding (right-pad columns and synthetic batch rows) must not claim
            # routed-expert capacity — mask it out of the token stream
            token_mask = (jnp.arange(prompt_len)[None] < lengths[:, None]) & row_valid[:, None]
            hidden, cache = apply(p, tokens, positions, cache, token_mask)
            self.chunk_attention_path = self._traced.chunk or self.chunk_attention_path
            last = jnp.take_along_axis(hidden, (lengths - 1)[:, None, None], axis=1)[:, 0]
            tok0 = sample_tokens(constrain(head(p, last), cstate), key, config)
            return tok0, cache, last.astype(jnp.float32)

        def prefill_chunk(p, tokens, start, lengths, cache, row_valid, last):
            """One chunk of a long-context prefill: columns [start, start+C) of the
            padded prompt flow through the cache (attention sees all previously
            written slots). ``last`` ``[B, dim]`` f32 accumulates the hidden row of
            each example's last real token: a row whose last token falls inside
            this chunk takes it, the others pass through. The third output is what
            the module counted over the chunk (``counter_names``; empty without)."""
            self.prefill_traces += 1
            p = dequant(p)
            batch, chunk = tokens.shape
            positions = start + jnp.broadcast_to(jnp.arange(chunk)[None], (batch, chunk))
            token_mask = (positions < lengths[:, None]) & row_valid[:, None]
            hidden, cache, counts = apply_counted(p, tokens, positions, cache, token_mask)
            self.chunk_attention_path = self._traced.chunk or self.chunk_attention_path
            sel = positions == (lengths - 1)[:, None]  # at most one true column per row
            chunk_last = jnp.einsum("blc,bl->bc", hidden.astype(jnp.float32), sel.astype(jnp.float32))
            return jnp.where(sel.any(axis=1)[:, None], chunk_last, last), cache, counts

        def first_token(p, last, key, *cstate):
            """Sample the first generated token from accumulated last-row hiddens
            (chunked-prefill epilogue; everything but lm_head is DCE'd)."""
            p = dequant(p)
            return sample_tokens(constrain(head(p, last.astype(compute_dtype)), cstate), key, config)

        def decode_steps(p, cache, tok, lengths, done, key, *cstate, steps: int):
            """Roll ``steps`` decode steps from the carry; returns the new tokens
            ``[B, steps]``, each sampled token's log-probability ``[B, steps]``
            f32 (under the constrained policy distribution — the OpenAI
            ``logprobs`` surface reads these; done rows report 0.0), and the
            advanced carry. One ``lax.scan`` compile per distinct ``steps``
            value — __call__ always uses max_new_tokens - 1 and stream() a
            fixed chunk size, so the trace set stays tiny. With constraints the
            carry gains each row's DFA state as its tail element; ``steps`` is
            keyword-only so both carry layouts share this signature. A module
            that counts (``counter_names``) ends the carry in one more element:
            what it counted over this dispatch's steps (the incoming value, the
            dispatch before's, is dropped)."""
            self.decode_traces += 1
            eos = config.eos_id
            if names:
                cstate = (*cstate[:-1], jnp.zeros((len(names),), jnp.int32))

            def body(carry, _):
                cache, tok, lengths, done, key, *cst = carry
                key, sub = jax.random.split(key)
                ps = dequant(p)  # per-step so int8, not bf16, is the steady-state HBM read
                positions = lengths[:, None]  # each example's next free cache slot
                hidden, cache, counts = apply_counted(ps, tok[:, None], positions, cache, (~done)[:, None])
                if names:
                    cst[-1] = merge_counts(cst[-1], counts)
                logits = constrain(head(ps, hidden[:, 0]), cst)
                nxt = sample_tokens(logits, sub, config)
                # the chosen token's logprob rides along (one gather + one
                # logsumexp over logits the head already materialized — noise
                # next to the matmul); done rows' pad "samples" report 0.0
                lp = jnp.take_along_axis(
                    jax.nn.log_softmax(logits, axis=-1), nxt[:, None], axis=1
                )[:, 0]
                lp = jnp.where(done, jnp.float32(0.0), lp)
                if cs is not None:
                    # done rows hold their state (their sampled token is a pad)
                    cst[0] = jnp.where(done, cst[0], self._cs_trans[cst[0], nxt])
                nxt = jnp.where(done, jnp.int32(config.pad_id), nxt)
                lengths = lengths + jnp.where(done, 0, 1)
                if eos is not None:
                    done = done | (nxt == eos)
                return (cache, nxt, lengths, done, key, *cst), (nxt, lp)

            carry, (toks, lps) = jax.lax.scan(
                body, (cache, tok, lengths, done, key, *cstate), None, length=steps
            )
            self.decode_attention_path = self._traced.read
            self.decode_state_path = self._traced.state
            # the advanced carry (incl. cache) is returned so the donated input
            # buffers have outputs to alias with — one cache in HBM throughout
            return toks.T, lps.T, carry

        # donate the cache through both stages: one cache lives in HBM, not two
        self._prefill = jax.jit(prefill, donate_argnums=(3,))
        self._prefill_chunk = jax.jit(prefill_chunk, donate_argnums=(4, 6))
        self._first_token = jax.jit(first_token)
        self._decode = jax.jit(decode_steps, static_argnames=("steps",), donate_argnums=(1,))
        self._apply_fn = apply  # for engines composing on top (beam search)
        self._head_fn = head
        self._beam_fns: dict = {}
        self._sp_prefill_fn = None
        self._spec_engine = None  # lazily built when config.draft is set
        #: AOT program store (serving/aot.py): set by :meth:`enable_aot`, after
        #: which the jitted programs above resolve load-before-compile
        self._aot_store = None

    # ------------------------------------------------------------------ AOT preload

    def _aot_context(self) -> dict:
        """The key parts that pin a serialized executable to THIS generator's
        programs: module architecture, generation config (kv dtype, buckets,
        sampling law — all compiled into the programs), quantization mode,
        mesh topology, and — because grammar tables are traced in as
        constants — a digest of the constraint set's tables."""
        import hashlib as _hashlib

        from unionml_tpu.serving.aot import mesh_context

        ctx = {
            "module": type(self.module).__name__,
            "module_config": repr(getattr(self.module, "config", None)),
            "generation_config": repr(self.config),
            "quantize": self.quantize,
            # bumped when a program's OUTPUT signature changes (the decode
            # scan gained a logprobs output, the prefill chunk merges the
            # last-hidden row itself): stale serialized executables from an
            # older layout must miss and recompile, not load
            "program_abi": "decode-logprobs-v3-chunk-last",
            **mesh_context(self.mesh),
        }
        if self._cs is not None:
            digest = _hashlib.sha256()
            digest.update(np.asarray(self._cs_trans).tobytes())
            digest.update(np.asarray(self._cs_allowed).tobytes())
            ctx["constraints"] = digest.hexdigest()
        return ctx

    def enable_aot(self, store: Any) -> "Generator":
        """Route this generator's jitted programs (``_prefill`` per bucket,
        ``_prefill_chunk``, ``_first_token``, ``_decode``, and the lazily
        built sequence-parallel prefill) through an AOT
        :class:`~unionml_tpu.serving.aot.ProgramStore`: every distinct call
        signature resolves load-before-compile, and every compile that does
        happen is serialized back so the next cold process loads it. Tokens
        are bit-identical either way — a loaded executable IS the program a
        fresh compile would produce. Idempotent; ``None`` is a no-op."""
        if store is None or self._aot_store is not None:
            return self
        from unionml_tpu.serving.aot import AOTFunction

        ctx = self._aot_context()
        self._aot_store = store
        self._prefill = AOTFunction(self._prefill, "prefill", store, ctx)
        self._prefill_chunk = AOTFunction(self._prefill_chunk, "prefill_chunk", store, ctx)
        self._first_token = AOTFunction(self._first_token, "first_token", store, ctx)
        self._decode = AOTFunction(
            self._decode, "decode", store, ctx, static_argnames=("steps",)
        )
        return self

    def warmup(self) -> "Generator":
        """Resolve the batch-1 prefill program for every configured prompt
        bucket plus one decode scan — through the AOT store when
        :meth:`enable_aot` armed one (load-before-compile; a populated store
        makes this load-bound), as a plain compile otherwise. The serving
        engines have their own richer warmup; this is the standalone
        ``Generator`` analog the serverless batch path and notebooks use."""
        cfg = self.config
        vocab = int(getattr(self.module.config, "vocab_size", 2))
        tok = 1 % max(vocab, 1)
        decoded = False
        for bucket in sorted(set(cfg.prompt_buckets)):
            _, _, _, carry = self._start([[tok] * bucket], 0)
            if not decoded and cfg.max_new_tokens >= 2:
                # one scan covers every bucket: the cache width is shared
                # (cache_len keys off the WIDEST bucket), so decode is one
                # program regardless of which bucket prefilled the carry
                self._decode(self.params, *carry, steps=cfg.max_new_tokens - 1)
                decoded = True
        return self

    def _speculative(self):
        """The internal speculative engine for ``config.draft`` — reuses THIS
        generator (params already quantized/placed) as the verify target."""
        if self._spec_engine is None:
            from unionml_tpu.models.speculative import SpeculativeGenerator

            self._spec_engine = SpeculativeGenerator.from_target(self, self.config.draft)
        return self._spec_engine

    # ------------------------------------------------------------------ helpers

    def _build_sp_prefill(self):
        """Sequence-parallel prefill: the decoder runs under shard_map with its
        ring/ulysses attention over the ``sequence`` axis, per-layer post-RoPE
        K/V are sown out, and shard_map's output stitching yields the global
        K/V to write into the cache. One jit per prompt-bucket shape."""
        import dataclasses as _dc

        from jax.sharding import PartitionSpec as P

        from unionml_tpu.models.layers import quantize_kv_rows

        cfg = self.config
        mesh = self.mesh
        if has_slot_planes(self.module.config):
            raise ValueError("sp_prefill over a model that keeps a recurrent state a slot: the state runs through the sequence it would split")
        sp_module = type(self.module)(_dc.replace(self.module.config, attention_impl=cfg.sp_prefill))
        n_layers = self.module.config.n_layers
        #: what each layer's attention sows for the cache: its layout's planes (keys and values; a latent layer's one)
        planes = tuple(cache_layout(self.module.config))
        compute_dtype = getattr(self.module.config, "dtype", jnp.bfloat16)
        data_axes = tuple(a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1) or None

        def local_fwd(tokens_local, mask_local, p):
            seq_idx = jax.lax.axis_index("sequence")
            local_len = tokens_local.shape[1]
            positions = seq_idx * local_len + jnp.arange(local_len)
            hidden, variables = sp_module.apply(
                {"params": p},
                tokens_local,
                positions,
                return_hidden=True,
                token_mask=mask_local,
                mutable=["kvs"],
            )
            kvs = variables["kvs"]
            return hidden, tuple({name: kvs[f"layer_{i}"]["attn"][name][0] for name in planes} for i in range(n_layers))

        tok_spec = P(data_axes, "sequence")
        act_spec = P(data_axes, "sequence", None)
        kv_spec = P(data_axes, "sequence", None, None)
        out_specs = (act_spec, ({name: kv_spec for name in planes},) * n_layers)
        in_specs = (tok_spec, tok_spec, P())
        wrapped = jax.shard_map(
            local_fwd, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
        )

        def sp_prefill(p, tokens, lengths, cache, key, row_valid, *cstate):
            self.prefill_traces += 1
            p = self._dequant_params(p)
            # pad columns and synthetic batch rows must not claim routed-expert
            # capacity — same contract as the dense prefill's token_mask
            token_mask = (jnp.arange(tokens.shape[1])[None] < lengths[:, None]) & row_valid[:, None]
            hidden, sown = wrapped(tokens, token_mask, p)
            new_cache = []
            for layer, rows in zip(cache, sown):
                new_layer = {}
                for name in planes:
                    if f"{name}_scale" in layer:
                        values, scales = quantize_kv_rows(rows[name])
                        written = {name: values, f"{name}_scale": scales}
                    else:
                        written = {name: rows[name].astype(layer[name].dtype)}
                    for plane, value in written.items():  # a sown row narrower than its plane leaves the tail zeros
                        new_layer[plane] = jax.lax.dynamic_update_slice(layer[plane], value, (0, 0, 0, 0))
                new_cache.append(new_layer)
            last = jnp.take_along_axis(hidden, (lengths - 1)[:, None, None], axis=1)[:, 0]
            logits = self._constrain(self._head_fn(p, last.astype(compute_dtype)), cstate)
            tok0 = sample_tokens(logits, key, cfg)
            return tok0, tuple(new_cache), last.astype(jnp.float32)

        jitted = jax.jit(sp_prefill, donate_argnums=(3,))
        if self._aot_store is not None:
            from unionml_tpu.serving.aot import AOTFunction

            return AOTFunction(jitted, "sp_prefill", self._aot_store, self._aot_context())
        return jitted

    def _bucket(self, max_prompt: int) -> int:
        for b in sorted(self.config.prompt_buckets):
            if b >= max_prompt:
                return b
        # oversized prompt: one extra trace at the next multiple of 64, logged
        bucket = int(math.ceil(max_prompt / 64) * 64)
        logger.info(f"prompt length {max_prompt} exceeds configured buckets; padding to {bucket}")
        return bucket

    def _plane_shardings(self, cache: Any, paged: Callable[[Any, Optional[str]], Any], rows: Optional[str]) -> Any:
        """A cache's placements, leaf for leaf: a plane paged by position where ``paged(leaf,
        model)`` says, a slot plane ``[rows, *shape]`` with its heads' axis over ``model`` and its
        rows over ``rows`` (a mesh axis or None), a table whole."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        model = "model" if "model" in self.mesh.axis_names else None
        layouts = cache_layouts(self.module.config, self.config.kv_cache_dtype)

        def spec(a: Any, plane: Any) -> NamedSharding:
            if not isinstance(plane, SlotPlane):
                return NamedSharding(self.mesh, P() if plane is None else paged(a, model))
            axes = [None] * len(plane.shape)
            if model is not None and plane.model_axis is not None and plane.shape[plane.model_axis] % self.mesh.shape["model"] == 0:
                axes[plane.model_axis] = model
            return NamedSharding(self.mesh, P(rows, *axes))

        return tuple({name: spec(a, layout.get(name)) for name, a in layer.items()} for layer, layout in zip(cache, layouts))

    def _cache_shardings(self, cache: Any) -> Any:
        """Where a contiguous ``[B, L, H, last]`` cache lives on the mesh, leaf for
        leaf (shapes are enough: a program's ``out_shardings`` are built from
        these); ``None`` without a mesh."""
        if self.mesh is None:
            return None
        from jax.sharding import PartitionSpec as P

        data = "data" if "data" in self.mesh.axis_names else None

        def paged(a: Any, model: Optional[str]) -> Any:
            if model is not None and a.shape[2] % self.mesh.shape["model"] != 0:
                model = None  # KV heads not divisible by the model axis: replicate heads
            return P(data, None, model, None)

        return self._plane_shardings(cache, paged, data)

    def _place_cache(self, cache: Any) -> Any:
        if self.mesh is None:
            return cache
        return jax.tree_util.tree_map(jax.device_put, cache, self._cache_shardings(cache))

    def _place_paged_cache(self, cache: Any) -> Any:
        """Mesh placement for a PAGED pool (:func:`init_paged_cache`): the
        heads-major ``[H_kv, n_blocks, block_size, D]`` pools shard their head
        dim over the model axis — the same axis the dense ``[B, L, H, D]``
        cache shards in :meth:`_place_cache` — and the ``[slots, max_blocks]``
        block tables replicate (every shard needs the full table to gather its
        own heads' blocks). A slot plane ``[slots, *shape]`` shards the axis its
        layout names as its heads'."""
        if self.mesh is None:
            return cache
        from jax.sharding import PartitionSpec as P

        def paged(a: Any, model: Optional[str]) -> Any:
            if model is not None and a.shape[0] % self.mesh.shape["model"] != 0:
                model = None  # KV heads indivisible by the axis: replicate
            return P(model)

        return jax.tree_util.tree_map(jax.device_put, cache, self._plane_shardings(cache, paged, None))

    # ------------------------------------------------------------------ generate

    def cache_prefix(self, prefix_tokens: Sequence[int]) -> PrefixCache:
        """Prefill a shared prompt prefix once and return its K/V rows for reuse:
        pass the result as ``prefix=`` to :meth:`__call__` / :meth:`stream` and
        only the per-request suffix is prefilled — the system-prompt cost is paid
        here, not per request."""
        p0 = len(prefix_tokens)
        if p0 == 0:
            raise ValueError("prefix_tokens must be non-empty")
        if has_slot_planes(self.module.config):
            raise ValueError(
                "cache_prefix over a model that keeps a row a slot (a recurrent state): a prefix's rows are "
                "cut by position, and such a state has no position axis to cut"
            )
        _, _, _, carry = self._start([list(prefix_tokens)], 0)
        cache = carry[0]
        return PrefixCache(
            layers=jax.tree_util.tree_map(lambda c: c[:1, :p0], cache),
            length=p0,
            tokens=tuple(int(t) for t in prefix_tokens),
        )

    def _start(
        self,
        prompts: Sequence[Sequence[int]],
        seed: int,
        extra_cache: int = 0,
        batch_override: Optional[int] = None,
        prefix: Optional[PrefixCache] = None,
        constraint: Optional[Any] = None,
    ):
        """Shared prefill setup: pad/bucket the prompts, allocate + place the cache,
        run prefill, and return the first sampled token, the last-token hidden
        states, and the decode carry. ``batch_override`` pins the padded batch
        exactly (beam search needs batch == groups * num_beams). With ``prefix``,
        the cached prefix rows are pasted into every row's cache and only the
        suffix is prefilled (through the chunked path, which takes a start
        offset). ``constraint`` (an int or one int per prompt) selects each row's
        grammar from ``config.constraints``; rows then start at that grammar's
        DFA start state and the carry gains the per-row state as its tail."""
        cfg = self.config
        if constraint is not None and self._cs is None:
            raise ValueError("constraint= requires GenerationConfig.constraints to be set")
        n = len(prompts)
        if prefix is not None and any(len(p) == 0 for p in prompts):
            # an empty suffix would silently condition on prefix + [pad_id]
            # (lengths are clamped to >= 1 below); bare continuation from a
            # prefix would need the prefix's last-token hidden, which
            # cache_prefix does not keep
            raise ValueError("prompts must be non-empty when prefix= is given")
        lengths = np.array([max(len(p), 1) for p in prompts], np.int32)
        bucket = self._bucket(int(lengths.max()))
        if batch_override is not None:
            if batch_override < n:
                raise ValueError(f"batch_override {batch_override} < {n} prompts")
            batch = batch_override
        else:
            # pad the batch to a power of two so XLA sees few batch shapes — and to
            # a multiple of the mesh's data axis so the cache batch dim shards evenly
            batch = 1 << max(0, (n - 1).bit_length())
            if self.mesh is not None and "data" in self.mesh.axis_names:
                data = int(self.mesh.shape["data"])
                batch = int(math.ceil(batch / data) * data)
        tokens = np.full((batch, bucket), cfg.pad_id, np.int32)
        for i, p in enumerate(prompts):
            tokens[i, : len(p)] = np.asarray(p, np.int32)
        all_lengths = np.ones((batch,), np.int32)
        all_lengths[:n] = lengths

        cstate: tuple = ()
        if self._cs is not None:
            cstate = (jnp.asarray(self._cs.start_states(self._grammar_ids(constraint, n, batch))),)

        sp = (
            cfg.sp_prefill
            and self.mesh is not None
            and int(self.mesh.shape.get("sequence", 1)) > 1
        )
        chunk = cfg.prefill_chunk
        if prefix is not None:
            # composition with sp_prefill: the LONG shared prefix was prefilled
            # sequence-parallel inside cache_prefix (its _start call dispatches
            # to the sp path); the short per-request suffix goes through the
            # offset chunked path here — SP where length lives, cache reuse
            # where repetition lives
            return self._start_with_prefix(
                prefix, tokens, lengths, batch, n, bucket, extra_cache, seed, cstate
            )
        if sp:
            seq = int(self.mesh.shape["sequence"])
            aligned = chunk_aligned(bucket, seq)  # each sequence shard gets equal columns
            tokens = np.pad(tokens, ((0, 0), (0, aligned - tokens.shape[1])), constant_values=cfg.pad_id)
            bucket = aligned
        elif chunk:
            bucket = chunk_aligned(bucket, chunk)  # bucket shape is moot once chunked
            tokens = np.pad(tokens, ((0, 0), (0, bucket - tokens.shape[1])), constant_values=cfg.pad_id)
        cache_len = max(bucket, max(cfg.prompt_buckets, default=0)) + cfg.max_new_tokens + extra_cache
        cache = self._place_cache(
            init_cache(self.module.config, batch, cache_len, kv_dtype=cfg.kv_cache_dtype)
        )
        key = jax.random.PRNGKey(seed)
        key, prefill_key = jax.random.split(key)
        row_valid = jnp.arange(batch) < n
        if sp:
            if self._sp_prefill_fn is None:
                self._sp_prefill_fn = self._build_sp_prefill()
            tok0, cache, last = self._sp_prefill_fn(
                self.params, jnp.asarray(tokens), jnp.asarray(all_lengths), cache, prefill_key, row_valid, *cstate
            )
        elif chunk and bucket > chunk:
            last, cache = self._chunked_prefill_loop(
                tokens, jnp.asarray(all_lengths), cache, row_valid, chunk
            )
            tok0 = self._first_token(self.params, last, prefill_key, *cstate)
        else:
            tok0, cache, last = self._prefill(
                self.params, jnp.asarray(tokens), jnp.asarray(all_lengths), cache, prefill_key, row_valid, *cstate
            )
        return self._finish_prefill(n, tok0, last, cache, jnp.asarray(all_lengths), row_valid, key, cstate)

    def _chunked_prefill_loop(self, tokens, lengths_dev, cache, row_valid, chunk: int, start: int = 0):
        """Run right-padded ``tokens`` through the chunked prefill fn in
        ``chunk``-column slices whose absolute positions begin at ``start``,
        accumulating each row's last-real-token hidden state."""
        last = jnp.zeros((tokens.shape[0], self.module.config.dim), jnp.float32)
        for c in range(0, tokens.shape[1], chunk):
            last, cache, _ = self._prefill_chunk(
                self.params,
                jnp.asarray(tokens[:, c : c + chunk]),
                jnp.int32(start + c),
                lengths_dev,
                cache,
                row_valid,
                last,
            )
        return last, cache

    def _grammar_ids(self, constraint: Optional[Any], n: int, batch: int) -> np.ndarray:
        """Normalize a ``constraint=`` argument (int, or one int per prompt) to
        per-row grammar ids; synthetic padding rows ride FREE (id 0)."""
        gids = np.zeros((batch,), np.int64)
        if constraint is not None:
            con = np.asarray(constraint)
            if con.ndim == 0:
                gids[:n] = int(con)
            elif con.shape[0] == n:
                gids[:n] = con
            else:
                raise ValueError(f"constraint has {con.shape[0]} entries for {n} prompts")
        return gids

    def _finish_prefill(self, n, tok0, last, cache, lengths_dev, row_valid, key, cstate=()):
        eos = self.config.eos_id
        done = (tok0 == eos) if eos is not None else jnp.zeros(tok0.shape, bool)
        # synthetic batch-padding rows start done: they emit pads, never advance
        # their cache, and stay out of routed-expert capacity
        done = done | ~row_valid
        carry = (cache, tok0, lengths_dev, done, key)
        if cstate:
            # advance each row's DFA past its (constrained) first token; the
            # state rides as the carry's tail through the decode scan
            carry = carry + (self._cs_trans[cstate[0], tok0],)
        if self.counter_names:
            carry = carry + (jnp.zeros((len(self.counter_names),), jnp.int32),)
        return n, tok0, last, carry

    def _start_with_prefix(
        self,
        prefix: PrefixCache,
        tokens: np.ndarray,
        lengths: np.ndarray,
        batch: int,
        n: int,
        bucket: int,
        extra_cache: int,
        seed: int,
        cstate: tuple = (),
    ):
        """Prefill only the per-request suffix: the prefix's K/V rows are pasted
        into slots ``[0, p0)`` of every cache row and the suffix flows through the
        chunked-prefill path with a start offset of ``p0`` (its positions — hence
        RoPE phases and visibility — continue where the prefix left off). The
        shared system-prompt cost was paid once in :meth:`cache_prefix`."""
        cfg = self.config
        p0 = prefix.length
        chunk = cfg.prefill_chunk or bucket
        aligned = chunk_aligned(bucket, chunk)
        if aligned > tokens.shape[1]:
            tokens = np.pad(
                tokens, ((0, 0), (0, aligned - tokens.shape[1])), constant_values=cfg.pad_id
            )
        cache_len = (
            p0 + max(aligned, max(cfg.prompt_buckets, default=0)) + cfg.max_new_tokens + extra_cache
        )
        cache = self._place_cache(
            init_cache(self.module.config, batch, cache_len, kv_dtype=cfg.kv_cache_dtype)
        )
        cache = _paste_prefix_rows(cache, prefix.layers)
        key = jax.random.PRNGKey(seed)
        key, prefill_key = jax.random.split(key)
        row_valid = jnp.arange(batch) < n
        # total sequence length = prefix + suffix; synthetic rows pretend one
        # suffix token (they are masked out of the forward via row_valid anyway)
        all_lengths = np.full((batch,), p0 + 1, np.int32)
        all_lengths[:n] = p0 + lengths
        lengths_dev = jnp.asarray(all_lengths)
        last, cache = self._chunked_prefill_loop(
            tokens, lengths_dev, cache, row_valid, chunk, start=p0
        )
        tok0 = self._first_token(self.params, last, prefill_key, *cstate)
        return self._finish_prefill(n, tok0, last, cache, lengths_dev, row_valid, key, cstate)

    def __call__(
        self,
        prompts: Sequence[Sequence[int]],
        *,
        seed: int = 0,
        prefix: Optional[PrefixCache] = None,
        constraint: Optional[Any] = None,
    ) -> np.ndarray:
        """Generate ``max_new_tokens`` per prompt; returns ``[len(prompts), max_new]``
        int32 (``pad_id`` after each example's ``eos_id``). With ``prefix`` (from
        :meth:`cache_prefix`), prompts are suffixes after the shared prefix and
        only they are prefilled. With ``config.draft`` set, decoding runs
        speculatively (same output law, fewer target dispatches). ``constraint``
        (an int, or one int per prompt, indexing ``config.constraints``; 0 = the
        FREE grammar) masks each row's decoding by its grammar's token DFA."""
        if self.config.draft is not None:
            return self._speculative()(prompts, seed=seed, prefix=prefix, constraint=constraint)
        n, tok0, _, carry = self._start(prompts, seed, prefix=prefix, constraint=constraint)
        steps = self.config.max_new_tokens - 1
        first = np.asarray(tok0)[:, None]
        if steps <= 0:
            return first[:n]
        rest, _, _ = self._decode(self.params, *carry, steps=steps)
        return np.concatenate([first, np.asarray(rest)], axis=1)[:n]

    def beam_search(
        self,
        prompts: Sequence[Sequence[int]],
        *,
        num_beams: int = 4,
        length_penalty: float = 0.0,
        constraint: Optional[Any] = None,
    ) -> np.ndarray:
        """Deterministic beam search: returns the highest-sum-log-prob continuation
        of ``max_new_tokens`` per prompt (``[n_prompts, max_new]`` int32).

        Beams are batch rows: each prompt is prefilled ``num_beams`` times and the
        whole search runs as ONE jitted ``lax.scan`` — each step scores all beams,
        takes the top ``num_beams`` of the ``num_beams * vocab`` candidates per
        prompt, and physically gathers the KV cache rows to the surviving parents
        (decode streams the weights anyway; the cache gather is a small fraction
        of the step's HBM traffic). A beam that emits ``eos_id`` is finished: it
        keeps competing with its score frozen, padding from there on. With
        ``length_penalty`` > 0 final scores are divided by
        ``((5 + len) / 6) ** length_penalty`` (GNMT convention).

        ``constraint`` (an int or one per prompt, indexing ``config.constraints``)
        runs the search inside the grammar: each beam carries its DFA state
        (gathered alongside cache rows on reorder), candidate scores are the
        log-probs of the CONSTRAINED policy (logits masked by the beam's
        allowed set, then renormalized — the same distribution sampling draws
        from), and EOS competes only at accepting states.
        """
        cfg = self.config
        if num_beams < 1:
            raise ValueError("num_beams must be >= 1")
        n = len(prompts)
        # pad whole GROUPS (not rows) so the batch is exactly groups * num_beams;
        # a multiple of the data axis keeps both the prefill batch (groups) and
        # the search batch (groups * num_beams) shardable
        groups = 1 << max(0, (n - 1).bit_length())
        if self.mesh is not None and "data" in self.mesh.axis_names:
            data = int(self.mesh.shape["data"])
            groups = int(math.ceil(groups / data) * data)
        # prefill each UNIQUE prompt once (synthetic padding groups get _start's
        # row_valid masking, keeping them out of routed-expert capacity), then
        # tile every cache row to its num_beams slots — beams share the prompt
        _, _, last, carry = self._start(prompts, 0, batch_override=groups, constraint=constraint)
        cache, lengths = carry[0], carry[2]
        tile = jnp.arange(groups * num_beams) // num_beams
        cache = jax.tree_util.tree_map(lambda c: c[tile], cache)
        last, lengths = last[tile], lengths[tile]
        done = tile >= n  # synthetic groups only
        cstate = ()
        if self._cs is not None:
            # the search seeds from the PREFILL distribution (not _start's
            # sampled tok0), so every beam starts at its grammar's START state
            gids = self._grammar_ids(constraint, n, groups)
            cstate = (jnp.asarray(self._cs.start_states(gids))[tile],)
        fn = self._beam_fns.get(num_beams)
        if fn is None:
            fn = self._build_beam_fn(num_beams)
            self._beam_fns[num_beams] = fn
        out, scores, _ = fn(self.params, cache, last, lengths, done, *cstate)
        out = np.asarray(out).reshape(groups, num_beams, -1)[:n]
        scores = np.asarray(scores).reshape(groups, num_beams)[:n]
        if cfg.eos_id is not None and length_penalty > 0.0:
            lens = np.where(out == cfg.eos_id, 1, 0).argmax(axis=2)
            lens = np.where((out == cfg.eos_id).any(axis=2), lens + 1, out.shape[2])
            scores = scores / (((5.0 + lens) / 6.0) ** length_penalty)
        best = scores.argmax(axis=1)
        return out[np.arange(n), best]

    def _build_beam_fn(self, num_beams: int):
        cfg = self.config
        eos = cfg.eos_id
        pad = jnp.int32(cfg.pad_id)
        cs = self._cs

        def beam_fn(p, cache, last, lengths, done, *cstate):
            p = self._dequant_params(p)
            batch = last.shape[0]
            groups = batch // num_beams
            compute_dtype = getattr(getattr(self.module, "config", None), "dtype", jnp.bfloat16)

            def logprobs(hidden, st=None):
                logits = self._head_fn(p, hidden)
                if st is not None:
                    # the CONSTRAINED policy's distribution: mask, then
                    # renormalize — the same law sampling draws from
                    logits = self._constrain(logits, (st,))
                return jax.nn.log_softmax(logits, axis=-1)

            st = cstate[0] if cs is not None else None
            # first expansion from the PREFILL distribution: all beams of a group
            # share the prompt, so its top tokens seed distinct beams. With
            # num_beams > vocab only vocab distinct seeds exist; the surplus beams
            # start at -inf and join the pool as the tree widens in later steps.
            lp0 = logprobs(last.astype(compute_dtype), st).reshape(groups, num_beams, -1)
            vocab = lp0.shape[-1]
            k0 = min(num_beams, vocab)
            seed_scores, seed_tokens = jax.lax.top_k(lp0[:, 0], k0)  # [G, k0]
            scores = jnp.pad(seed_scores, ((0, 0), (0, num_beams - k0)), constant_values=-jnp.inf)
            first_tokens = jnp.pad(seed_tokens, ((0, 0), (0, num_beams - k0)), constant_values=int(pad))
            tok = jnp.where(done, pad, first_tokens.reshape(batch))
            beam_done = done | ((tok == eos) if eos is not None else jnp.zeros_like(done))
            out = jnp.full((batch, cfg.max_new_tokens), pad, jnp.int32).at[:, 0].set(tok)
            if cs is not None:
                st = jnp.where(done, st, self._cs_trans[st, tok])

            def body(carry, col):
                cache, tok, lengths, scores, beam_done, out, *cst = carry
                # feed each beam's pending token (decode convention: positions =
                # filled length; lengths advance after the feed)
                hidden, cache = self._apply_fn(
                    p, tok[:, None], lengths[:, None], cache, (~beam_done)[:, None]
                )
                lengths = lengths + jnp.where(beam_done, 0, 1)
                lp = logprobs(hidden[:, 0], cst[0] if cs is not None else None)
                lp = lp.reshape(groups, num_beams, vocab)
                flat_done = beam_done.reshape(groups, num_beams)
                # finished beams contribute exactly one frozen-score candidate
                # (their pad continuation); active beams expand over the vocab
                cand = scores[:, :, None] + jnp.where(flat_done[:, :, None], -jnp.inf, lp)
                pad_cand = jnp.where(flat_done, scores, -jnp.inf)  # [G, K]
                all_cand = jnp.concatenate([cand.reshape(groups, -1), pad_cand], axis=1)
                top_scores, top_idx = jax.lax.top_k(all_cand, num_beams)  # [G, K]
                is_pad_cand = top_idx >= num_beams * vocab
                parent = jnp.where(is_pad_cand, top_idx - num_beams * vocab, top_idx // vocab)
                token = jnp.where(is_pad_cand, pad, top_idx % vocab)

                # reorder every per-beam tensor to the surviving parents
                flat_parent = (jnp.arange(groups)[:, None] * num_beams + parent).reshape(batch)
                cache = jax.tree_util.tree_map(lambda c: c[flat_parent], cache)
                out = out[flat_parent]
                lengths = lengths[flat_parent]
                prev_done = beam_done[flat_parent]
                tok = token.reshape(batch)
                beam_done = prev_done | ((tok == eos) if eos is not None else jnp.zeros_like(prev_done))
                out = jax.vmap(lambda row, t: row.at[col].set(t))(out, jnp.where(prev_done, pad, tok))
                if cs is not None:
                    # DFA states follow their parent beams, then advance on the
                    # freshly chosen token (pad candidates keep their state)
                    stp = cst[0][flat_parent]
                    cst = (jnp.where(prev_done, stp, self._cs_trans[stp, tok]),)
                return (cache, tok, lengths, top_scores, beam_done, out, *cst), None

            carry = (cache, tok, lengths, scores, beam_done, out) + ((st,) if cs is not None else ())
            steps = cfg.max_new_tokens - 1
            if steps > 0:
                carry, _ = jax.lax.scan(body, carry, jnp.arange(1, steps + 1))
            cache, tok, lengths, scores, beam_done, out = carry[:6]
            # the final cache rides along so the donated input can alias
            return out, scores.reshape(batch), cache

        return jax.jit(beam_fn, donate_argnums=(1,))

    def stream(
        self,
        prompts: Sequence[Sequence[int]],
        *,
        seed: int = 0,
        chunk_size: int = 16,
        prefix: Optional[PrefixCache] = None,
        constraint: Optional[Any] = None,
    ):
        """Incremental generation: yields ``[len(prompts), <=chunk_size]`` arrays of
        newly decoded tokens as they materialize (the first yield is the single
        prompt-sampled token). The decode compiles once per ``chunk_size``; when
        every row has emitted ``eos_id`` the stream ends early. Total tokens across
        yields equal ``__call__``'s output for the same seed. ``prefix`` works as
        in :meth:`__call__`. With ``config.draft`` set, streaming is speculative
        and yields follow :meth:`SpeculativeGenerator.stream`'s RAGGED shape (a
        list of per-row 1-D arrays) since rows advance at round granularity."""
        cfg = self.config
        if chunk_size < 1:
            raise ValueError("chunk_size must be >= 1")
        if cfg.draft is not None:
            yield from self._speculative().stream(
                prompts, seed=seed, chunk_size=chunk_size, prefix=prefix, constraint=constraint
            )
            return
        # the last chunk may overshoot max_new_tokens; give its cache writes room
        n_chunks = max(0, -(-(cfg.max_new_tokens - 1) // chunk_size))
        extra = n_chunks * chunk_size - (cfg.max_new_tokens - 1)
        n, tok0, _, carry = self._start(
            prompts, seed, extra_cache=extra, prefix=prefix, constraint=constraint
        )
        yield np.asarray(tok0)[:n, None]
        produced = 1
        while produced < cfg.max_new_tokens:
            if bool(np.asarray(carry[3]).all()):
                return  # every row finished with eos
            toks, _, carry = self._decode(self.params, *carry, steps=chunk_size)
            take = min(chunk_size, cfg.max_new_tokens - produced)
            yield np.asarray(toks)[:n, :take]
            produced += take
