"""Weight-only int8 quantization correctness."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu.models import GenerationConfig, Generator, Llama, LlamaConfig
from unionml_tpu.ops.quant import QuantizedTensor, dequantize, dequantize_tree, quantize_array, quantize_params


def _flat_by_path(tree):
    """{'a/b/c': leaf} view of a (possibly quantized) params tree."""
    return {
        "/".join(str(getattr(p, "key", p)) for p in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: isinstance(x, QuantizedTensor)
        )[0]
    }


def test_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    w = rng.normal(size=(256, 512)).astype(np.float32) * rng.uniform(0.01, 10, size=(1, 512))
    qt = quantize_array(w)
    assert qt.q.dtype == jnp.int8 and qt.q.shape == w.shape
    back = np.asarray(dequantize(qt, jnp.float32))
    # symmetric per-channel int8: error per element <= scale/2 = abs_max/254
    col_max = np.abs(w).max(axis=0)
    assert (np.abs(back - w) <= col_max / 254 + 1e-6).all()


def test_quantize_params_selects_matmul_kernels_only():
    config = LlamaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    module = Llama(config)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    qparams = quantize_params(params, min_size=1)

    flat = _flat_by_path(qparams)
    assert isinstance(flat["layer_0/attn/q_proj/kernel"], QuantizedTensor)
    assert isinstance(flat["layer_0/mlp/wi/kernel"], QuantizedTensor)
    assert isinstance(flat["lm_head/kernel"], QuantizedTensor)
    assert not isinstance(flat["embed/embedding"], QuantizedTensor)  # gathers, not matmuls
    assert not isinstance(flat["final_norm/scale"], QuantizedTensor)


def test_quantized_forward_stays_close():
    config = LlamaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    module = Llama(config)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    tokens = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)

    ref = module.apply({"params": params}, tokens)
    deq = dequantize_tree(quantize_params(params, min_size=1), dtype=jnp.float32)
    out = module.apply({"params": deq}, tokens)
    # logits drift stays small relative to the logits' own scale
    denom = float(jnp.abs(ref).max())
    assert float(jnp.abs(out - ref).max()) / denom < 0.05


def test_quantized_generation_runs_and_is_deterministic():
    config = LlamaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    module = Llama(config)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,)),
        quantize="int8",
    )
    prompts = [[5, 6, 7], [1, 2, 3, 4, 5, 6]]
    out = gen(prompts)
    assert out.shape == (2, 8)
    np.testing.assert_array_equal(out, gen(prompts))


def test_int8_matmul_kernel_matches_dequant_reference():
    """Pallas kernel (interpret mode on CPU) vs dequant + dot, several shapes
    incl. M needing padding; an untileable shape is an error under
    ``impl="pallas"``, never a quiet dequant path."""
    from unionml_tpu.ops.int8_matmul import int8_matmul, quantized_matmul

    rng = np.random.default_rng(1)
    for m, k, f in [(8, 256, 512), (5, 512, 1536), (130, 128, 256)]:
        qt = quantize_array(rng.normal(size=(k, f)).astype(np.float32))
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        ref = np.asarray(x) @ (np.asarray(qt.q, np.float32) * np.asarray(qt.scale))
        out = np.asarray(int8_matmul(x, qt.q, qt.scale, out_dtype=jnp.float32, interpret=True))
        scale_ref = np.abs(ref).max() + 1e-9
        assert np.abs(out - ref).max() / scale_ref < 0.01  # bf16 x-cast rounding

    # untileable weight shape: the asked-for kernel cannot run, and says so
    qt = quantize_array(rng.normal(size=(96, 100)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(4, 96)), jnp.float32)
    with pytest.raises(ValueError, match="no block tiling"):
        quantized_matmul(x, qt, out_dtype=jnp.float32, impl="pallas")
    out = quantized_matmul(x, qt, out_dtype=jnp.float32)
    ref = np.asarray(x) @ (np.asarray(qt.q, np.float32) * np.asarray(qt.scale))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)

    # batched leading dims flow through
    x3 = jnp.asarray(rng.normal(size=(2, 3, 96)), jnp.float32)
    out3 = quantized_matmul(x3, qt, out_dtype=jnp.float32)
    assert out3.shape == (2, 3, 100)


def test_stacked_expert_kernels_get_per_expert_scales():
    """[E, K, F] expert stacks reduce only the contraction axis: per-(expert,
    channel) scales, so one outlier expert cannot crush the others' resolution."""
    rng = np.random.default_rng(3)
    w = rng.normal(size=(4, 32, 16)).astype(np.float32)
    w[2] *= 100.0  # outlier expert
    qt = quantize_array(w)
    assert qt.scale.shape == (4, 1, 16)
    back = np.asarray(dequantize(qt, jnp.float32))
    # per-expert error bound: each expert's channels quantize against its own max
    for e in range(4):
        col_max = np.abs(w[e]).max(axis=0)
        assert (np.abs(back[e] - w[e]) <= col_max / 254 + 1e-6).all(), e


def test_moe_int8_generation_runs_and_router_stays_fp():
    """MoE int8: stacked [E, K, F] expert kernels quantize (sized above the
    Generator's default min_size so generation really runs the int8 path) and
    dequant in-jit; the (precision-sensitive, f32-by-design) router never does."""
    from unionml_tpu.models import MoEConfig, MoETransformer

    # experts wi: [4, 128, 128] = 65536 elements >= Generator's min_size
    config = MoEConfig.tiny(
        vocab_size=61, dim=128, n_heads=4, n_kv_heads=2, hidden_dim=128,
        n_experts=4, k=2, capacity_factor=8.0, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    module = MoETransformer(config)
    params = module.init(jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))["params"]

    flat = _flat_by_path(quantize_params(params))  # Generator's own defaults
    assert isinstance(flat["layer_0/moe/experts/wi/kernel"], QuantizedTensor)
    assert flat["layer_0/moe/experts/wi/kernel"].scale.shape == (4, 1, 128)
    assert not isinstance(flat["layer_0/moe/router/kernel"], QuantizedTensor)

    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(16,)),
        quantize="int8",
    )
    assert any(
        isinstance(leaf, QuantizedTensor)
        for leaf in jax.tree_util.tree_leaves(gen.params, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    )
    out = gen([[3, 1, 4], [1, 5, 9, 2]])
    assert out.shape == (2, 6)
    np.testing.assert_array_equal(out, gen([[3, 1, 4], [1, 5, 9, 2]]))


def test_int8_kv_cache_logits_stay_close():
    """Prefill through an int8 KV cache must reproduce the fp-cache logits to
    per-(position, head) int8 quantization error (~1%)."""
    from unionml_tpu.models import init_cache

    config = LlamaConfig.tiny(
        vocab_size=64, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    module = Llama(config)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    tokens = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(8)[None], (1, 8))

    ref, _ = module.apply(
        {"params": params}, tokens, positions=positions, cache=init_cache(config, 1, 16)
    )
    out, qcache = module.apply(
        {"params": params}, tokens, positions=positions, cache=init_cache(config, 1, 16, kv_dtype="int8")
    )
    assert qcache[0]["k"].dtype == jnp.int8 and qcache[0]["k_scale"].shape == (1, 16, 2, 1)
    denom = float(jnp.abs(ref).max())
    assert float(jnp.abs(out - ref).max()) / denom < 0.02


def test_int8_kv_cache_generation_runs_and_composes_with_int8_weights():
    config = LlamaConfig.tiny(
        vocab_size=64, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    module = Llama(config)
    params = module.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,), kv_cache_dtype="int8"),
        quantize="int8",
    )
    prompts = [[5, 6, 7], [1, 2, 3, 4]]
    out = gen(prompts)
    assert out.shape == (2, 8)
    np.testing.assert_array_equal(out, gen(prompts))
    # streaming path shares the cache machinery
    chunks = list(gen.stream(prompts, chunk_size=3))
    assert np.concatenate(chunks, axis=1).shape[1] <= 8


def test_quantize_params_min_size_and_path_filters():
    """The selection edges serving depends on: ``min_size`` keeps small
    kernels full precision (a tiny model quantizes NOTHING under the default
    threshold — no silent accuracy tax for no bandwidth win), and the
    include/exclude regexes retarget selection without touching the tree
    walk."""
    config = LlamaConfig.tiny(
        vocab_size=61, dim=64, n_layers=1, n_heads=4, n_kv_heads=2, hidden_dim=128,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    module = Llama(config)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    # default min_size (1 << 16): every kernel of this tiny config is smaller,
    # so the tree passes through untouched
    untouched = _flat_by_path(quantize_params(params))
    assert not any(isinstance(leaf, QuantizedTensor) for leaf in untouched.values())
    # threshold boundary: exactly min_size elements quantizes (>=, not >)
    wi = _flat_by_path(params)["layer_0/mlp/wi/kernel"]
    boundary = int(np.prod(wi.shape))
    flat = _flat_by_path(quantize_params(params, min_size=boundary))
    assert isinstance(flat["layer_0/mlp/wi/kernel"], QuantizedTensor)

    # include narrows to one projection; everything else stays fp
    flat = _flat_by_path(quantize_params(params, include=r"q_proj/kernel$", min_size=1))
    assert isinstance(flat["layer_0/attn/q_proj/kernel"], QuantizedTensor)
    assert not isinstance(flat["layer_0/attn/k_proj/kernel"], QuantizedTensor)
    assert not isinstance(flat["lm_head/kernel"], QuantizedTensor)

    # exclude carves the head out of the default include
    flat = _flat_by_path(quantize_params(params, exclude=r"(embed|norm|lm_head)", min_size=1))
    assert not isinstance(flat["lm_head/kernel"], QuantizedTensor)
    assert isinstance(flat["layer_0/attn/q_proj/kernel"], QuantizedTensor)


def test_quantized_shardings_strip_axes_on_unit_dims():
    """_quantized_shardings: the int8 values keep the kernel's resolved
    sharding while the per-channel scale keeps mesh axes ONLY on its non-unit
    dims — a size-1 reduction dim carrying a mesh axis would be an invalid
    sharding. Covers the 2D kernel and the stacked [E, K, F] expert case
    (whose scale is [E, 1, F]: the middle axis must strip, the outer ones
    survive)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from unionml_tpu.models.generate import _quantized_shardings

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    rng = np.random.default_rng(5)
    qparams = {
        "dense": quantize_array(rng.normal(size=(32, 16)).astype(np.float32)),
        "experts": quantize_array(rng.normal(size=(4, 32, 16)).astype(np.float32)),
        "plain": jnp.zeros((8, 8), jnp.float32),
    }
    shardings = {
        "dense": NamedSharding(mesh, P("data", "model")),
        "experts": NamedSharding(mesh, P("data", None, "model")),
        "plain": NamedSharding(mesh, P(None, "model")),
    }
    fixed = _quantized_shardings(qparams, shardings, mesh)
    # dense kernel [32, 16] -> scale [1, 16]: the size-1 dim drops its axis
    assert fixed["dense"].q.spec == P("data", "model")
    assert fixed["dense"].scale.spec == P(None, "model")
    # expert stack [4, 32, 16] -> scale [4, 1, 16]: only the unit dim strips
    assert fixed["experts"].q.spec == P("data", None, "model")
    assert fixed["experts"].scale.spec == P("data", None, "model")
    # non-quantized leaves pass their sharding through untouched
    assert fixed["plain"].spec == P(None, "model")


def test_unsupported_mode_rejected():
    config = LlamaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    module = Llama(config)
    params = module.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    with pytest.raises(ValueError, match="int8"):
        Generator(module, params, GenerationConfig(), quantize="fp4")
