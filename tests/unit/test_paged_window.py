"""The sliding-window decode read over a paged cache (ops/paged_attention.py ``paged_window_decode_attention``):
where the read begins, and that the merge of the kernel's part with the gathered edge page is the masked softmax.

The library kernel runs on a TPU alone, so here a plain ``jax.numpy`` function with the same contract (normalized
output, running maximum, sum of exponentials; a row of length 0 reads nothing) stands in for it; the real one is
compiled for a described v5e in ``tests/emulated/test_chip_compile.py`` and run by ``chip_smoke.py``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu.ops import paged_attention as ops

PAGE, WINDOW = 4, 12
H_KV, GROUPS, D = 2, 3, 8


@pytest.mark.parametrize("length,edge_page,edge_start,tail", [
    (1, 0, 0, 0), (4, 0, 0, 0), (5, 0, 0, 1), (12, 0, 0, 8),  # shorter than the window: from page 0
    (13, 0, 1, 9), (15, 0, 3, 11), (16, 1, 4, 8), (17, 1, 5, 9), (40, 7, 28, 8), (43, 7, 31, 11),
])
def test_window_split(length, edge_page, edge_start, tail):
    got = ops.window_split(jnp.asarray([length]), WINDOW, PAGE)
    assert [int(v[0]) for v in got] == [edge_page, edge_start, tail]
    assert tail <= WINDOW - 1 and edge_start - edge_page * PAGE < PAGE  # the tail fits window / page pages


def _stats_stand_in(q, k_pages, v_pages, lengths, page_indices, *, pages_per_compute_block):
    assert page_indices.shape[1] % pages_per_compute_block == 0
    keys = jnp.moveaxis(k_pages[:, page_indices], 0, 1).reshape(q.shape[0], H_KV, -1, D).astype(jnp.float32)
    values = jnp.moveaxis(v_pages[:, page_indices], 0, 1).reshape(q.shape[0], H_KV, -1, D).astype(jnp.float32)
    logits = jnp.einsum("bhgd,bhtd->bhgt", q.reshape(q.shape[0], H_KV, GROUPS, D), keys)
    seen = jnp.arange(keys.shape[2])[None] < lengths[:, None]
    logits = jnp.where(seen[:, None, None], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.where(seen[:, None, None], jnp.exp(logits - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgt,bhtd->bhgd", p, values) / jnp.where(l > 0, l, 1.0)
    shape = (q.shape[0], H_KV * GROUPS)
    return o.reshape(*shape, D), m.reshape(*shape, 1), l.reshape(*shape, 1)


@pytest.mark.parametrize("table_pages", [6, 12], ids=["table_near_window", "table_wider"])
def test_windowed_read_is_the_masked_softmax(monkeypatch, table_pages):
    monkeypatch.setattr(ops, "_paged_attention_stats", _stats_stand_in)
    rng = np.random.default_rng(0)
    lengths = np.array([1, 3, 4, 5, 11, 12, 13, 15, 16, 17, 20, 23, 24], np.int32)
    lengths = lengths[lengths <= table_pages * PAGE]
    batch, n_pages = len(lengths), 256
    k_pages = jnp.asarray(rng.normal(size=(H_KV, n_pages, PAGE, D)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(H_KV, n_pages, PAGE, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(batch, H_KV * GROUPS, D)), jnp.float32)
    table = jnp.asarray(rng.permutation(n_pages)[: batch * table_pages].reshape(batch, table_pages), jnp.int32)
    got = ops.paged_window_decode_attention(q, k_pages, v_pages, jnp.asarray(lengths), table, window=WINDOW)

    keys = np.moveaxis(np.asarray(k_pages)[:, np.asarray(table)], 0, 1).reshape(batch, H_KV, -1, D)
    values = np.moveaxis(np.asarray(v_pages)[:, np.asarray(table)], 0, 1).reshape(batch, H_KV, -1, D)
    logits = np.einsum("bhgd,bhtd->bhgt", np.asarray(q).reshape(batch, H_KV, GROUPS, D), keys) * D**-0.5
    at = np.arange(keys.shape[2])[None]
    seen = (at < lengths[:, None]) & (at >= lengths[:, None] - WINDOW)
    logits = np.where(seen[:, None, None], logits, -np.inf)
    weights = np.exp(logits - logits.max(-1, keepdims=True))
    want = np.einsum("bhgt,bhtd->bhgd", weights / weights.sum(-1, keepdims=True), values).reshape(batch, -1, D)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_the_library_kernel_still_takes_what_the_windowed_read_hands_it():
    """``_paged_attention_stats`` launches ``jax.experimental``'s kernel body itself (its public wrapper drops the
    softmax statistics the merge needs), so it copies the wrapper's layout of prefetched scalars, operands, outputs
    and scratch. Another JAX may move them: this is the tier-1 trip-wire that says so by name, before a chip does."""
    import inspect

    from jax.experimental.pallas.ops.tpu.paged_attention import paged_attention_kernel as lib

    names = list(inspect.signature(lib.paged_flash_attention_kernel_inline_seq_dim).parameters)
    assert names == [
        "lengths_ref", "page_indices_ref", "buffer_index_ref", "init_flag_ref",  # the four prefetched scalars
        "q_ref", "k_pages_hbm_ref", "k_scales_pages_hbm_ref", "v_pages_hbm_ref", "v_scales_pages_hbm_ref",  # operands
        "o_ref", "m_ref", "l_ref",  # outputs: the wrapper keeps the first alone
        "k_vmem_buffer", "k_scales_vmem_buffer", "v_vmem_buffer", "v_scales_vmem_buffer", "k_sems", "v_sems",  # scratch
        "batch_size", "pages_per_compute_block", "pages_per_sequence", "mask_value", "attn_logits_soft_cap", "megacore_mode",
    ], f"jax {jax.__version__} changed the paged-attention kernel's signature: re-derive ops/paged_attention.py's launch"
    assert hasattr(lib, "DEFAULT_MASK_VALUE")
