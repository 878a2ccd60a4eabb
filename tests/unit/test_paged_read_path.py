"""Which read serves a paged decode (ops/paged_attention.py ``paged_read_path``).

On a TPU a single-token read over bf16 pages on one device goes through the
pallas paged-attention kernel; everything the kernel cannot serve keeps the
portable gather. These tests run on the CPU backend, so ``impl="auto"`` must
take the gather in every case — same tokens as ``impl="xla"`` — and the engine
must say so. (The kernel path itself is compiled for a described v5e in
``tests/emulated/test_chip_compile.py`` and run by ``chip_smoke.py``.)
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu import MeshSpec
from unionml_tpu.models import DraftSpec, GenerationConfig, Generator, Llama, LlamaConfig, llama_partition_rules
from unionml_tpu.ops.paged_attention import GATHER, PAGED_KERNEL, paged_read_path, paged_read_scope
from unionml_tpu.serving import ContinuousBatcher

PROMPTS = [[3, 14, 15, 92, 6], [27, 1], [8, 2, 8, 1, 8, 2, 8]]


def _tiny(impl, vocab=96, dim=64, seed=0):
    config = LlamaConfig.tiny(
        vocab_size=vocab, dim=dim, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
        attention_impl=impl, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    module = Llama(config)
    return module, module.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]


def _serve(impl, case):
    """Tokens of PROMPTS through a paged ContinuousBatcher, and what it says of its decode read."""
    module, params = _tiny(impl)
    cfg = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,))
    kwargs = {}
    if case == "int8_pages":
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    elif case == "speculative_verify":  # the target's decode read is the verify pass: L = gamma + 1
        draft, draft_params = _tiny(impl, dim=32, seed=1)
        cfg = dataclasses.replace(cfg, draft=DraftSpec(module=draft, params=draft_params, gamma=3))
    elif case == "mesh":
        kwargs = dict(mesh=MeshSpec(data=1, model=2).build(devices=jax.devices()[:2]), partition_rules=llama_partition_rules())
    batcher = ContinuousBatcher(Generator(module, params, cfg, **kwargs), slots=3, decode_chunk=4, block_size=4)
    try:
        tokens = [[int(t) for chunk in batcher.submit(p) for t in np.asarray(chunk).ravel()] for p in PROMPTS]
        return tokens, batcher.stats(), batcher.engine_log.snapshot(0)
    finally:
        batcher.close()


@pytest.mark.parametrize("case", ["cpu_backend", "int8_pages", "speculative_verify", "mesh"])
def test_auto_gathers_where_the_kernel_cannot_serve(case):
    if case == "mesh" and len(jax.devices()) < 2:
        pytest.skip("needs 2 emulated devices")
    tokens, stats, debug = _serve("auto", case)
    forced, forced_stats, _ = _serve("xla", case)
    assert tokens == forced and all(len(t) == 8 for t in tokens)
    assert stats["decode_attention_path"] == forced_stats["decode_attention_path"] == GATHER
    assert debug["decode_attention_path"] == GATHER  # what GET /debug/engine serves
    assert stats["decode_dispatches"] > 0


def test_a_contiguous_cache_has_no_paged_read():
    module, params = _tiny("auto")
    gen = Generator(module, params, GenerationConfig(max_new_tokens=4, temperature=0.0, prompt_buckets=(16,)))
    gen(PROMPTS[:1])
    assert gen.decode_traces == 1 and gen.decode_attention_path is None


def _path(impl, backend, monkeypatch, *, length=1, dtype=jnp.bfloat16, head_dim=128, quantized=False, sharded=None):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    q = jax.ShapeDtypeStruct((2, length, 8, head_dim), jnp.bfloat16)
    pages = jax.ShapeDtypeStruct((2, 9, 16, head_dim), jnp.int8 if quantized else dtype)
    if sharded is None:
        return paged_read_path(impl, q, pages, quantized=quantized), None
    with paged_read_scope(sharded=sharded) as recorded:
        return paged_read_path(impl, q, pages, quantized=quantized), recorded


RULE = {
    # (impl, backend, what differs from a one-token bf16 read on one device) -> path
    "auto_tpu": (dict(impl="auto", backend="tpu"), PAGED_KERNEL),
    "auto_tpu_mesh_of_one": (dict(impl="auto", backend="tpu", sharded=False), PAGED_KERNEL),
    "auto_cpu": (dict(impl="auto", backend="cpu"), GATHER),
    "auto_gpu": (dict(impl="auto", backend="gpu"), GATHER),
    "auto_tpu_verify": (dict(impl="auto", backend="tpu", length=4), GATHER),
    "auto_tpu_int8": (dict(impl="auto", backend="tpu", quantized=True), GATHER),
    "auto_tpu_sharded": (dict(impl="auto", backend="tpu", sharded=True), GATHER),
    "auto_tpu_f32_pages": (dict(impl="auto", backend="tpu", dtype=jnp.float32), GATHER),
    "auto_tpu_narrow_head": (dict(impl="auto", backend="tpu", head_dim=64), GATHER),
    "xla_tpu": (dict(impl="xla", backend="tpu"), GATHER),
    "flash_cpu": (dict(impl="flash", backend="cpu"), PAGED_KERNEL),  # forced: the described-chip compile test
    "flash_cpu_verify": (dict(impl="flash", backend="cpu", length=4), GATHER),
    "flash_cpu_int8": (dict(impl="flash", backend="cpu", quantized=True), GATHER),
}


@pytest.mark.parametrize("name", sorted(RULE))
def test_the_rule_follows_what_the_trace_observes(name, monkeypatch):
    kwargs, want = RULE[name]
    kwargs = dict(kwargs)
    path, recorded = _path(kwargs.pop("impl"), kwargs.pop("backend"), monkeypatch, **kwargs)
    assert path == want
    assert recorded is None or recorded == [want]
