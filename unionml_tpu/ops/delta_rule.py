"""The gated delta rule with a decay a channel (Kimi Delta Attention, arXiv:2510.26692),
as one recurrent update and as its chunked form.

A head keeps a state ``S`` in ``R^{d_k x d_v}`` (key channel x value channel),
float32, and a token with query ``q``, key ``k`` (both ``[d_k]``), value ``v``
(``[d_v]``), log-decay ``g`` (``[d_k]``, ``<= 0``) and write strength ``beta``
(a scalar in ``[0, 1]``) does::

    S' = Diag(exp(g)) S          # forget, channel by channel
    S  = S' + beta k (v - S'^T k)^T   # move what S' holds under k towards v
    o  = S^T q

:func:`delta_rule_step` is that, one token a row (decode), written for XLA;
:func:`delta_rule_step_kernel` is the same step as one Pallas kernel for a TPU,
and :func:`state_step_path` says which of the two a trace takes.
:func:`delta_rule_chunked` gives the same numbers for ``L`` tokens without
``L`` sequential rank-one updates: inside a chunk of ``C`` positions, with
``G_r = sum_{i<=r} g_i`` and ``D(r, i) = exp(G_r - G_i)`` (``i <= r``, so never
above 1), the pseudo-values ``v~_r = beta_r (v_r - S_r'^T k_r)`` solve one unit
lower-triangular system::

    A[r, i] = beta_r sum_c k_r[c] k_i[c] D(r, i)[c]      (i < r)
    (I + A) V~ = Diag(beta) (V - (K * exp(G)) S0)
    o_r   = (q_r * exp(G_r))^T S0 + sum_{i<=r} (sum_c q_r[c] k_i[c] D(r, i)[c]) v~_i
    S_end = Diag(exp(G_C)) S0 + sum_i (k_i * D(C, i)) v~_i^T

and the chunks follow each other in a ``lax.scan`` that carries ``S`` alone;
everything that does not read ``S`` (``A``, its solve, the query-key products)
is computed for all chunks at once.

**The products with ``D``** are matrix products only where ``D(r, i)`` factors
as ``exp(G_r - G*) * exp(G* - G_i)``; ``exp(-G_i)`` alone overflows after a few
positions. So a chunk's ``CHUNK = 64`` rows go in sub-blocks of ``SUB = 16``
positions and ``G*`` is the running sum before the row's sub-block: the row
factor is then at most 1, the column factor at most 1 for earlier sub-blocks and
at most ``exp(SUB * bound)`` inside the row's own. With ``g >= -5`` (the
configuration's ``kda_lower_bound``), ``16 x 5 = 80 < ln(float32 max) = 88.7``:
that is what the bound of -5 buys. ``MIN_LOG_DECAY`` is the loosest bound the
sub-blocks carry; the layer refuses a configuration below it.

Every product here runs at ``Precision.HIGHEST``: on a TPU a float32 product is
otherwise rounded to bfloat16 operands, which the state is float32 to avoid;
the rule is ~5 MFLOP a token a layer, so the passes cost little beside the
layer's projections.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST
CHUNK, SUB = 64, 16
#: the lowest log-decay a position may have: ``exp(SUB * -g)`` must stay a float32
MIN_LOG_DECAY = -88.0 / SUB
#: the two updates a one-token step can take, as ``stats()["decode_state_path"]`` names them
STATE_KERNEL = "state_kernel"
STATE_XLA = "state_xla"
#: heads a grid step of :func:`delta_rule_step_kernel` carries (PERF.md section 6, PR 36)
BLOCK_HEADS = 16


def delta_rule_step(
    state: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """One token a row: ``state [B, H, d_k, d_v]`` float32, ``q``, ``k``, ``g``
    ``[B, H, d_k]``, ``v`` ``[B, H, d_v]``, ``beta`` ``[B, H]`` -> ``(o [B, H,
    d_v] float32, state)``. A row with ``beta = 0`` and ``g = 0`` keeps its state
    bit for bit. Written as multiplies and sums over the state, not as
    matrix-vector products: the step is bound by reading and writing the state,
    and the sums stay float32 on every backend."""
    state = state.astype(jnp.float32)
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    # both sums read the state as it came in (the decay moved onto the key and the query), so one pass over
    # it gives both, and a second reads it and writes it back: S^T q = S'^T q + (beta k . q) (v - S'^T k)
    decay = jnp.exp(g)
    held = jnp.sum(state * (decay * k)[..., None], axis=-2)  # S'^T k: what the decayed state holds under this key
    seen = jnp.sum(state * (decay * q)[..., None], axis=-2)  # S'^T q
    write, strength = v - held, beta[..., None] * k
    out = seen + jnp.sum(strength * q, axis=-1, keepdims=True) * write
    return out, decay[..., None] * state + strength[..., None] * write[..., None, :]


def state_step_path(state: jax.Array) -> str:
    """Which update serves a one-token step over ``state [B, H, d_k, d_v]``, decided from what the trace can
    observe and recorded in the enclosing :func:`~unionml_tpu.ops.paged_attention.paged_read_scope`: the kernel
    on a TPU, over a float32 state whose tile is whole lanes and sublanes (``d_k``, ``d_v`` multiples of 128)
    and whose heads split into blocks, held on one device (a custom call cannot be partitioned over a mesh);
    :func:`delta_rule_step` everywhere else."""
    from unionml_tpu.ops.paged_attention import read_scope

    sharded, paths = read_scope()
    heads, d_k, d_v = state.shape[-3:]
    kernel = (
        jax.default_backend() == "tpu"
        and not sharded
        and state.dtype == jnp.float32
        and d_k % 128 == 0
        and d_v % 128 == 0
        and heads % min(BLOCK_HEADS, heads) == 0
    )
    path = STATE_KERNEL if kernel else STATE_XLA
    if paths is not None:
        paths.append(path)
    return path


def _step_block(s_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, out_ref, s_out_ref):
    """One row's block of heads: each head's tile is read from VMEM once, for both sums and the update. The
    key-channel vectors multiply the tile down its sublanes, so they are laid out as columns (one transpose a
    block); the sums over ``d_k`` are sums over sublanes and come out as rows, as ``v`` is."""
    heads = s_ref.shape[1]
    q, k, v, g, beta = q_ref[0], k_ref[0], v_ref[0], g_ref[0], beta_ref[0]  # [heads, d]; beta [heads, 1]
    decay = jnp.exp(g)
    strength = beta * k
    gain = jnp.sum(strength * q, axis=-1, keepdims=True)  # strength . q: [heads, 1]
    cols = jnp.concatenate([decay * k, decay * q, decay, strength], axis=0).T  # [d_k, 4 heads]
    col = lambda i, h: cols[:, i * heads + h : i * heads + h + 1]  # noqa: E731
    outs = []
    for h in range(heads):
        s = s_ref[0, h]
        held = jnp.sum(s * col(0, h), axis=0, keepdims=True)  # S'^T k: [1, d_v]
        seen = jnp.sum(s * col(1, h), axis=0, keepdims=True)  # S'^T q
        write = v[h : h + 1] - held
        outs.append(seen + gain[h : h + 1] * write)
        s_out_ref[0, h] = col(2, h) * s + col(3, h) * write
    out_ref[0] = jnp.concatenate(outs, axis=0)


def delta_rule_step_kernel(
    state: jax.Array,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    *,
    block_heads: int = BLOCK_HEADS,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`delta_rule_step` as one Pallas kernel (``delta_rule_step`` in a device trace): the same operands,
    the same ``(o, state)``, float32 throughout. The grid walks rows x blocks of ``block_heads`` heads; each
    head's ``d_k x d_v`` tile is streamed from HBM once and written back once, in place (the state's input is
    its output's buffer), where XLA's lowering of the step reads it twice. ``state`` must be float32; the heads
    must split into blocks (:func:`state_step_path` says where the kernel serves)."""
    batch, heads, d_k, d_v = state.shape
    block = min(block_heads, heads)
    if state.dtype != jnp.float32 or heads % block:
        raise ValueError(f"the state step kernel takes a float32 state whose {heads} heads split into blocks of {block}")
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    vector = lambda d: pl.BlockSpec((1, block, d), lambda b, h: (b, h, 0))  # noqa: E731
    tile = pl.BlockSpec((1, block, d_k, d_v), lambda b, h: (b, h, 0, 0))
    out, state = pl.pallas_call(
        _step_block,
        grid=(batch, heads // block),
        in_specs=[tile, vector(d_k), vector(d_k), vector(d_v), vector(d_k), vector(1)],
        out_specs=[vector(d_v), tile],
        out_shape=[jax.ShapeDtypeStruct((batch, heads, d_v), jnp.float32), jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        name="delta_rule_step",
        interpret=interpret,
    )(state, q, k, v, g, beta[..., None])
    return out, state


def delta_rule_chunked(
    state: jax.Array,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    token_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """``L`` tokens a row: ``state [B, H, d_k, d_v]``, ``q``, ``k``, ``g`` ``[B,
    L, H, d_k]``, ``v`` ``[B, L, H, d_v]``, ``beta`` ``[B, L, H]``, ``token_mask``
    ``[B, L]`` (False: the position leaves the state untouched, as ``beta = 0``,
    ``g = 0`` does; its output row is unspecified) -> ``(o [B, L, H, d_v]
    float32, state after the last position)``. ``L`` need not be a multiple of
    ``CHUNK``: the tail is padded with masked positions. ``g`` must be at least
    ``MIN_LOG_DECAY`` everywhere (module docstring)."""
    chunk, sub = CHUNK, SUB
    batch, length, heads, d_k = q.shape
    d_v = v.shape[-1]
    state = state.astype(jnp.float32)
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    if token_mask is not None:
        beta = jnp.where(token_mask[..., None], beta, 0.0)
        g = jnp.where(token_mask[..., None, None], g, 0.0)
    pad = -length % chunk
    n, blocks = (length + pad) // chunk, chunk // sub

    def chunks(x: jax.Array) -> jax.Array:  # [B, L, H, ...] -> [B, H, n, C, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(batch, n, chunk, *x.shape[2:]), 3, 1)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    run = jnp.cumsum(g, axis=-2)  # G_r, inclusive: [B, H, n, C, d_k]
    before = jnp.concatenate([jnp.zeros_like(run[..., :1, :]), run[..., sub - 1 : -1 : sub, :]], axis=-2)  # G*: [.., blocks, d_k]
    by_block = lambda x: x.reshape(*x.shape[:-2], blocks, sub, x.shape[-1])  # noqa: E731
    row_decay = jnp.exp(by_block(run) - before[..., None, :])  # exp(G_r - G*) <= 1
    # exp(G* - G_i) for every position i up to the end of the row's sub-block (later ones are never read: zero)
    at = jnp.arange(chunk)
    reached = at[None, :] < (jnp.arange(blocks)[:, None] + 1) * sub  # [blocks, C]
    col_decay = jnp.exp(jnp.where(reached[..., None], before[..., None, :] - run[..., None, :, :], -jnp.inf))
    keys = k[..., None, :, :] * col_decay  # [.., blocks, C, d_k]
    pairs = lambda rows: jnp.einsum(  # noqa: E731
        "...brc,...bic->...bri", by_block(rows) * row_decay, keys, precision=_HIGHEST
    ).reshape(*rows.shape[:-1], chunk)  # sum_c rows_r[c] k_i[c] D(r, i)[c]: [.., C, C]
    a = jnp.where(at[:, None] > at[None, :], pairs(k), 0.0) * beta[..., None]
    qk = jnp.where(at[:, None] >= at[None, :], pairs(q), 0.0)

    decay = jnp.exp(run)
    rhs = jnp.concatenate([v, k * decay], axis=-1) * beta[..., None]
    solved = jax.scipy.linalg.solve_triangular(a + jnp.eye(chunk), rhs, lower=True, unit_diagonal=True)
    u, w = solved[..., :d_v], solved[..., d_v:]
    k_end = k * jnp.exp(run[..., -1:, :] - run)  # k_i * D(C, i)
    per_chunk = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731

    def body(s: jax.Array, xs: Tuple[jax.Array, ...]) -> Tuple[jax.Array, jax.Array]:
        u_, w_, qk_, q_in, k_end_, end_decay = xs
        pseudo = u_ - jnp.einsum("...rc,...cv->...rv", w_, s, precision=_HIGHEST)
        out = jnp.einsum("...rc,...cv->...rv", q_in, s, precision=_HIGHEST)
        out = out + jnp.einsum("...ri,...iv->...rv", qk_, pseudo, precision=_HIGHEST)
        s = end_decay[..., None] * s + jnp.einsum("...ic,...iv->...cv", k_end_, pseudo, precision=_HIGHEST)
        return s, out

    state, out = jax.lax.scan(
        body, state, tuple(per_chunk(x) for x in (u, w, qk, q * decay, k_end, decay[..., -1, :]))
    )
    out = jnp.moveaxis(out, 0, 2).reshape(batch, heads, n * chunk, d_v)[:, :, :length]
    return jnp.swapaxes(out, 1, 2), state
