"""The chip's compiler, without the chip: every shipped Pallas kernel is compiled for
a *described* TPU v5e at the shapes ``chip_smoke.py`` runs, so a kernel Mosaic
refuses fails here, at no chip time (interpret mode accepts layouts the chip does
not). Nothing executes; a compile that passes is not a chip run. Also: the smoke
itself must fail, and claim nothing, where there is no TPU."""

import functools
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from unionml_tpu.ops.flash_attention import flash_attention
from unionml_tpu.ops.int8_matmul import int8_matmul
from unionml_tpu.ops.paged_attention import paged_decode_attention, paged_window_decode_attention

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def chip():
    """One device of a described v5e 2x2; the persistent compilation cache is off
    around these tests (a chip-less process cannot read such entries back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    try:
        topology = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except (RuntimeError, NotImplementedError) as exc:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topology.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _flash(direction):
    def forward(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def backward(q, k, v):
        return jax.grad(lambda *a: forward(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    q, kv = ((4, 1024, 32, 128), jnp.bfloat16), ((4, 1024, 8, 128), jnp.bfloat16)  # GQA 32/8, D=128
    return (forward if direction == "fwd" else backward), [q, kv, kv]


def _int8_matmul(m):
    return int8_matmul, [((m, 4096), jnp.bfloat16), ((4096, 14336), jnp.int8), ((1, 14336), jnp.float32)]


def _paged(int8):
    pages = ((8, 512, 64, 128), jnp.int8 if int8 else jnp.bfloat16)  # 8 KV heads x 512 pages of 64
    args = [((8, 32, 128), jnp.bfloat16), pages, pages, ((8,), jnp.int32), ((8, 64), jnp.int32)]
    if not int8:
        return paged_decode_attention, args
    scales = ((8, 512, 64, 1), jnp.float32)

    def quantized(q, k, v, lengths, table, k_scales, v_scales):
        return paged_decode_attention(q, k, v, lengths, table, k_scales=k_scales, v_scales=v_scales)

    return quantized, args + [scales, scales]


def _paged_window():
    """A sliding layer's decode read at the afmoe cell's shape: 128 rows, 48 query / 8 KV heads of 128, a table of
    84 pages of 64 over a pool of 3,073, window 4,096 (the kernel's part: 64 pages, 16 a block)."""
    def windowed(q, k, v, lengths, table):
        return paged_window_decode_attention(q, k, v, lengths, table, window=4096)

    pages = ((8, 3073, 64, 128), jnp.bfloat16)
    return windowed, [((128, 48, 128), jnp.bfloat16), pages, pages, ((128,), jnp.int32), ((128, 84), jnp.int32)]


def _grouped_matmul(rows):
    """The held experts' product on the chip: the pallas grouped matmul, at 32 experts of 3072 x 3072 (a decode step of 128 slots: 512 rows; a chunk of 256 tokens: 1,024)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    def product(a, w, sizes):
        return gmm(a, w, sizes, preferred_element_type=jnp.bfloat16, tiling=(128, 1024, 1024))

    return product, [((rows, 3072), jnp.bfloat16), ((32, 3072, 3072), jnp.bfloat16), ((32,), jnp.int32)]


CASES = {
    "flash_fwd": _flash("fwd"),
    "flash_bwd": _flash("bwd"),
    "int8_matmul_m8": _int8_matmul(8),
    "int8_matmul_m1": _int8_matmul(1),
    "paged_decode_bf16": _paged(int8=False),
    "paged_decode_int8": _paged(int8=True),
    "paged_window_decode": _paged_window(),
    "grouped_matmul_decode_512": _grouped_matmul(512),
    "grouped_matmul_chunk_1024": _grouped_matmul(1024),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    fn, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip) for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()  # raises what the chip's compiler would raise
    assert "tpu_custom_call" in compiled.as_text(), "the Pallas kernel is not in the compiled program"


def _on_chip(chip, make):
    """The shapes ``make`` would return, as arguments that live on the described chip."""
    return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), jax.eval_shape(make))


def _mistral(layers=2):
    from unionml_tpu.models import Llama, LlamaConfig

    return Llama(LlamaConfig(
        vocab_size=32768, dim=4096, n_layers=layers, n_heads=32, n_kv_heads=8, hidden_dim=14336,
        max_seq_len=32768, rope_theta=1e6, attention_impl="flash", param_dtype=jnp.bfloat16,
    ))


def _afmoe():
    from unionml_tpu.models import AfmoeConfig, AfmoeTransformer

    return AfmoeTransformer(AfmoeConfig(
        vocab_size=25024, n_layers=3, n_dense_layers=1, experts_held=(0, 32), attention_impl="flash",
        layer_types=("sliding_attention", "sliding_attention", "full_attention"), param_dtype=jnp.bfloat16,
    ))


#: the serving cells' decode carries (perf/workloads/*.json): slots, pages a row, pool pages (scratch included)
DECODE_SHAPES = {"chat_sat_32x25": (32, 25, 513), "docs_16x56": (16, 56, 769)}


@pytest.mark.parametrize("shape", sorted(DECODE_SHAPES))
def test_decode_steps_compiles_for_v5e_without_a_gathered_copy(chip, shape):
    """The whole decode program at Mistral-7B's widths over the cells' paged caches, through
    the kernel read (forced: the backend here is the CPU, so ``auto`` would gather): Mosaic
    takes it, one kernel a layer, and no temporary as large as the gather path's logical copy
    (``[B, pages * 64, 32, 128]`` bf16) is left — nor a re-laid-out copy of a pool."""
    from unionml_tpu.models import GenerationConfig, Generator
    from unionml_tpu.models.generate import init_paged_cache

    slots, pages, pool = DECODE_SHAPES[shape]
    layers, page = 2, 64
    module = _mistral(layers)
    config, on_chip = module.config, functools.partial(_on_chip, chip)
    params = on_chip(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    cache = on_chip(lambda: init_paged_cache(config, slots, pool, page, pages, fill_block=pool - 1))
    tok, lengths, done = (jax.ShapeDtypeStruct((slots,), dtype, sharding=chip) for dtype in (jnp.int32, jnp.int32, jnp.bool_))
    gen = Generator(module, params, GenerationConfig(max_new_tokens=64, temperature=0.0))
    compiled = gen._decode.lower(params, cache, tok, lengths, done, on_chip(lambda: jax.random.PRNGKey(0)), steps=8).compile()

    text = compiled.as_text()
    assert gen.decode_attention_path == "paged_kernel"
    assert text.count("tpu_custom_call") == layers
    # left at two layers: the hoisted q/k/v projection transposes (96 MB) and a step's activations
    gathered = slots * pages * page * 32 * 128 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < gathered
    # the kernel reads the pools row-major; a write that made XLA keep them otherwise would copy each one, each step
    assert not re.search(rf"= bf16\[8,{pool},{page},128\]\S* copy\(", text)


def test_afmoe_decode_steps_compiles_for_v5e(chip):
    """The afmoe decode program at published widths (a dense sliding layer, a sliding and a full expert layer,
    32 held of 256 experts) over the cell's paged cache, through the kernel reads (forced, as above): Mosaic
    takes the windowed read, the plain read and — the trace's backend being the CPU, the routed product is
    ``ragged_dot`` here — XLA's own grouped kernel; the program counts its counters into the carry."""
    from unionml_tpu.models import GenerationConfig, Generator
    from unionml_tpu.models.generate import init_paged_cache

    slots, pages, pool, page = 128, 84, 3073, 64
    module = _afmoe()
    config, on_chip = module.config, functools.partial(_on_chip, chip)
    params = on_chip(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    cache = on_chip(lambda: init_paged_cache(config, slots, pool, page, pages, fill_block=pool - 1))
    assert cache[0]["k"].shape == (8, pool, page, 128)  # the published head width, not dim // n_heads = 64
    tok, lengths, done = (jax.ShapeDtypeStruct((slots,), dtype, sharding=chip) for dtype in (jnp.int32, jnp.int32, jnp.bool_))
    gen = Generator(module, params, GenerationConfig(max_new_tokens=64, temperature=0.0))
    counts = jax.ShapeDtypeStruct((len(gen.counter_names),), jnp.int32, sharding=chip)
    compiled = gen._decode.lower(params, cache, tok, lengths, done, on_chip(lambda: jax.random.PRNGKey(0)), counts, steps=8).compile()
    assert gen.decode_attention_path == "paged_kernel" and "decode_window_pages_skipped" in gen.counter_names
    text = compiled.as_text()
    assert text.count("paged_window_attention") >= 2  # the two sliding layers' reads
    assert not re.search(rf"= bf16\[8,{pool},{page},128\]\S* copy\(", text)  # no pool re-laid for a kernel
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 16e9


def _glm():
    from unionml_tpu.models import Glm4MoeLiteConfig, Glm4MoeLiteTransformer

    return Glm4MoeLiteTransformer(Glm4MoeLiteConfig(
        vocab_size=19360, n_layers=3, experts_held=(0, 8), attention_impl="flash", param_dtype=jnp.bfloat16,
    ))


def test_latent_decode_steps_compile_for_v5e_over_one_plane(chip):
    """The glm4_moe_lite decode program at published widths (a dense and two expert layers, 8 held of 64 experts) over
    the long_sat cell's paged latent cache — 48 slots, 140 pages a row, one plane of 640 (576 in whole lanes) a
    layer, no ``"v"`` — through the kernel read (forced, as above): Mosaic takes the library kernel with the latent
    pages as K and as V, one launch a layer; no pool is re-laid for it and no gathered copy of the rows' tables
    (``[48, 8960, 640]`` bf16) is left; the program counts its seven counters into the carry."""
    from unionml_tpu.models import GenerationConfig, Generator
    from unionml_tpu.models.generate import init_paged_cache

    slots, pages, pool, page = 48, 140, 3585, 64
    module = _glm()
    config, on_chip = module.config, functools.partial(_on_chip, chip)
    params = on_chip(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    cache = on_chip(lambda: init_paged_cache(config, slots, pool, page, pages, fill_block=pool - 1))
    assert set(cache[0]) == {"k", "table"} and cache[0]["k"].shape == (1, pool, page, 640)
    tok, lengths, done = (jax.ShapeDtypeStruct((slots,), dtype, sharding=chip) for dtype in (jnp.int32, jnp.int32, jnp.bool_))
    counts = jax.ShapeDtypeStruct((7,), jnp.int32, sharding=chip)
    gen = Generator(module, params, GenerationConfig(max_new_tokens=64, temperature=0.0))
    compiled = gen._decode.lower(params, cache, tok, lengths, done, on_chip(lambda: jax.random.PRNGKey(0)), counts, steps=8).compile()
    assert gen.decode_attention_path == "latent_paged_kernel" and gen.counter_names[-3] == "latent_positions_read"
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3  # one read a layer (the routed product is XLA's own here)
    assert not re.search(rf"= bf16\[1,{pool},{page},640\]\S* copy\(", text)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < slots * pages * page * 640 * 2
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 16e9


def _walks_the_row_in_blocks(text, layers, chunk, cache_len):
    """The compiled chunk program reads its row cache in key blocks: one ``while`` a layer, and no float32 array
    (scores, weights) whose key extent is the whole row."""
    return len(re.findall(r" while\(", text)) >= layers and not re.search(rf"f32\[[\d,]*{chunk},{cache_len}\]", text)


def test_latent_prefill_chunk_compiles_for_v5e(chip):
    """A 256-token chunk over an 8,960-position latent row cache (the cell's admission), by the absorbed read: the row
    is one plane ``[1, 8960, 1, 640]`` a layer (11 MB; XLA re-lays it once a layer on the way out, 0.03 ms at the
    chip's peak: PERF.md section 7), walked in key blocks by one ``while`` a layer (no ``[20, 256, 8960]`` array
    of scores exists, masked or not), and the program's temporaries stay under 0.5 GB (17 MB in this compile; the
    expanded read's up-projected row left 273 MB)."""
    from unionml_tpu.models import GenerationConfig, Generator
    from unionml_tpu.models.generate import init_cache

    module, chunk, cache_len = _glm(), 256, 8960
    on_chip = functools.partial(_on_chip, chip)

    def scalar(dtype, shape=()):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = on_chip(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    row = on_chip(lambda: init_cache(module.config, 1, cache_len))
    assert set(row[0]) == {"k"} and row[0]["k"].shape == (1, cache_len, 1, 640)
    gen = Generator(module, params, GenerationConfig(max_new_tokens=768, temperature=0.0, prompt_buckets=(256,)))
    args = (params, scalar(jnp.int32, (1, chunk)), scalar(jnp.int32), scalar(jnp.int32, (1,)), row, scalar(jnp.bool_, (1,)), scalar(jnp.float32, (1, 2048)))
    compiled = gen._prefill_chunk.lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
    assert _walks_the_row_in_blocks(compiled.as_text(), module.config.n_layers, chunk, cache_len)


#: the serving cells' engines (perf/configs/*.json "engine" + perf/workloads/*.json): module, widest prompt bucket,
#: answer budget, engine options, and the row's positions that follow from them (docs takes the radix hits; its
#: longest prompt, 3,200, pads to a bucket of 3,328)
ADMISSION_SHAPES = {
    "chat_sat": (_mistral, 1024, 512, {"slots": 32, "pool_blocks": 512}, 1544),
    "chat_wide_sat": (_afmoe, 4608, 768, {"slots": 160, "pool_blocks": 3072}, 5384),
    "docs": (_mistral, 3328, 64, {"slots": 16, "pool_blocks": 768}, 3584),
    "long_sat": (_glm, 8192, 768, {"slots": 48, "pool_blocks": 3584}, 8968),  # one plane, one head, 640 wide
}


@pytest.mark.parametrize("cell", sorted(ADMISSION_SHAPES))
def test_admission_programs_compile_for_v5e_without_a_copied_row(chip, cell):
    """The engine's own admission programs at a saturated cell's shapes (fewer layers): the set-up (length, key,
    flags, a zeroed row cache), a radix hit's set-up (the row gathered from the pool) and the Generator's chunk
    program (the last-hidden merge inside) compile for the described chip; none leaves a row-shaped ``copy`` (the
    rows are written once, where they stay). The paste and the hit's set-up move a row as whole pages: neither
    leaves a pool-shaped ``copy`` (a ``[H, last]`` slab a position made XLA re-lay every pool, there and back), and
    the paste's temporaries stay under one pool's bytes."""
    from unionml_tpu.models import GenerationConfig, Generator
    from unionml_tpu.models.generate import cache_layout, gather_paged_rows, init_paged_cache
    from unionml_tpu.serving import ContinuousBatcher

    make, max_prompt, max_new, options, cache_len = ADMISSION_SHAPES[cell]
    module, chunk, page = make(), 256, 64

    on_chip = functools.partial(_on_chip, chip)

    def scalar(dtype, shape=()):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = on_chip(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    cfg = GenerationConfig(max_new_tokens=max_new, temperature=0.0, prompt_buckets=tuple(range(chunk, max_prompt + 1, chunk)))
    gen = Generator(module, params, cfg)
    batcher = ContinuousBatcher(gen, decode_chunk=8, block_size=page, admit_chunk=chunk, prefix_cache=True, **options)
    try:
        heads, width = cache_layout(module.config)["k"][:2]
        assert batcher.cache_len == cache_len and batcher.max_blocks == -(-cache_len // page)
        row_copy = re.compile(rf"= bf16\[1,{batcher.cache_len},{heads},{width}\]\S* copy\(")
        pool_copy = re.compile(rf"= bf16\[{heads},{batcher.pool_blocks + 1},{page},{width}\]\S* copy\(")

        setup = batcher._setup_fn.lower(scalar(jnp.uint32), scalar(jnp.int32), ()).compile()
        assert not row_copy.search(setup.as_text())
        lengths, key, row_valid, (last,), (row,) = on_chip(lambda: batcher._setup_fn(jnp.uint32(0), jnp.int32(0), ()))
        assert row[0]["k"].shape == (1, batcher.cache_len, heads, width)

        pool = on_chip(lambda: init_paged_cache(
            module.config, batcher.slots, batcher.pool_blocks + 1, page, batcher.max_blocks, fill_block=batcher.pool_blocks
        ))
        table_row = scalar(jnp.int32, (batcher.max_blocks,))
        hit = batcher._cached_setup_fn.lower(pool, table_row, scalar(jnp.uint32), scalar(jnp.int32)).compile()
        gather = jax.jit(gather_paged_rows, static_argnums=(2,)).lower(pool, table_row, batcher.cache_len).compile()
        # the gather reads whole pages, in the pools' own layout: no pool is re-laid for it, bare or inside the set-up
        assert not pool_copy.search(gather.as_text()) and not pool_copy.search(hit.as_text())
        assert not row_copy.search(hit.as_text())

        carry = (scalar(jnp.int32, (batcher.slots,)), scalar(jnp.int32, (batcher.slots,)), scalar(jnp.bool_, (batcher.slots,)))
        paste = batcher._paged_admit_fn.lower(
            pool, row, *carry, scalar(jnp.int32), scalar(jnp.int32, (1,)), scalar(jnp.int32, (1,)), table_row, scalar(jnp.int32)
        ).compile()
        assert not pool_copy.search(paste.as_text())
        one_pool = heads * (batcher.pool_blocks + 1) * page * width * 2
        assert paste.memory_analysis().temp_size_in_bytes < one_pool

        chunk_args = (params, scalar(jnp.int32, (1, chunk)), scalar(jnp.int32), lengths, row, row_valid, last)
        step = gen._prefill_chunk.lower(*chunk_args).compile()
        # the merge's select is a [1, dim] row: the cache row is still written in place (donated), never copied
        # (but for the one-head latent row, which XLA re-lays once a layer on the way out: the latent chunk's test above)
        assert heads == 1 or not row_copy.search(step.as_text())
        # the chunk's read walks a long row in key blocks: no array of scores over the whole row ([48, 256, 5384],
        # [20, 256, 8968]); chat_sat's and docs' rows are short enough to be read whole, with no loop
        from unionml_tpu.ops.attention import ONE_TRIP_KEYS

        walked = _walks_the_row_in_blocks(step.as_text(), module.config.n_layers, chunk, batcher.cache_len)
        assert walked == (batcher.cache_len > ONE_TRIP_KEYS) == (cell in ("chat_wide_sat", "long_sat"))
    finally:
        batcher.close()


@pytest.mark.parametrize("mode", ["plain", "speculative"])
def test_warmup_leaves_no_admission_program_uncompiled(mode):
    """After ``warmup()`` a cold admission of every bucket, a multi-chunk one and (plain mode: the cache does not
    compose with speculation) a radix-hit one compile nothing: the set-up, the hit's set-up, the chunk program,
    ``first_token``, the paste and the decode program were all built by the warm-up's own requests."""
    from unionml_tpu.models import DraftSpec, GenerationConfig, Generator, Llama, LlamaConfig
    from unionml_tpu.serving import ContinuousBatcher

    def tiny(dim, layers, seed):
        module = Llama(LlamaConfig.tiny(
            vocab_size=97, dim=dim, n_layers=layers, n_heads=4, n_kv_heads=2, hidden_dim=2 * dim,
            dtype=jnp.float32, param_dtype=jnp.float32,
        ))
        return module, module.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]

    module, params = tiny(64, 2, 0)
    draft = None
    if mode == "speculative":
        d_module, d_params = tiny(32, 1, 9)
        draft = DraftSpec(module=d_module, params=d_params, gamma=3)
    cfg = GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(16,), draft=draft)
    batcher = ContinuousBatcher(
        Generator(module, params, cfg), slots=2, decode_chunk=2, block_size=8, admit_chunk=8, prefix_cache=draft is None
    )
    compiled = None

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(seconds)

    try:
        batcher.warmup()  # one bucket: no probe shares a prefix with another, so the warm-up makes its own hit
        compiled = []
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        long = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7]
        for prompt in (long, long[:11] + [2, 2], [5, 5, 5]):
            assert len([t for chunk in batcher.submit(prompt) for t in chunk]) == 6
        stats = batcher.stats()
        assert stats["prefill"]["chunks"] >= 4
        if draft is None:
            assert stats["prefix_cache"]["hits"] >= 1 and stats["prefix_cache"]["misses"] >= 1
        assert compiled == []
    finally:
        batcher.close()
        if compiled is not None:  # registered: the listener list is the process's
            from jax._src import monitoring

            monitoring.unregister_event_duration_listener(on_duration)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_and_claims_nothing_without_a_tpu(tmp_path, where):
    """No accelerator, or nothing of the repo beside the script: a non-zero exit and
    no success line — never a CPU run reported as a chip run."""
    script = REPO / "chip_smoke.py"
    cwd = REPO
    if where == "alone":
        cwd = tmp_path
        script = Path(shutil.copy(script, tmp_path))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run([sys.executable, str(script)], cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0, done.stdout[-2000:]
    assert '"ok": true' not in done.stdout


def _ling(**changes):
    """bailing_hybrid at Ling-3.0-flash's widths, cut to a dense KDA layer, an expert KDA layer and the expert MLA
    layer, routing group 0 (64 of 512 experts) and an eighth of the vocabulary held."""
    from unionml_tpu.models import BailingHybridConfig, BailingHybridTransformer

    return BailingHybridTransformer(BailingHybridConfig(**{**dict(
        vocab_size=19648, n_layers=3, layer_types=("kda", "kda", "mla"), n_dense_layers=1, experts_held=(0, 64),
        attention_impl="flash", param_dtype=jnp.bfloat16,
    ), **changes}))


def test_hybrid_decode_steps_compile_for_v5e_over_slot_state_and_latent_pages(chip):
    """The bailing_hybrid decode program at published widths over the think_sat cell's pool — 168 slots; a KDA
    layer's state ``[168, 32, 128, 128]`` float32 and tails a slot, no table; the MLA layer's one latent plane of
    9,409 pages with its table — compiles for the described chip with the latent read through the kernel, keeps
    no second copy of a layer's state (its temporaries stay under one layer's state) and counts its ten counters."""
    from unionml_tpu.models import GenerationConfig, Generator
    from unionml_tpu.models.generate import init_paged_cache

    slots, pool, page, pages = 168, 9409, 64, 57
    module = _ling()
    config, on_chip = module.config, functools.partial(_on_chip, chip)
    params = on_chip(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    cache = on_chip(lambda: init_paged_cache(config, slots, pool, page, pages, fill_block=pool - 1))
    assert set(cache[0]) == {"S", "conv"} and cache[0]["S"].shape == (slots, 32, 128, 128) and cache[0]["S"].dtype == jnp.float32
    assert cache[0]["conv"].shape == (slots, 3, 3, 4096) and set(cache[2]) == {"k", "table"} and cache[2]["k"].shape == (1, pool, page, 640)
    tok, lengths, done = (jax.ShapeDtypeStruct((slots,), dtype, sharding=chip) for dtype in (jnp.int32, jnp.int32, jnp.bool_))
    counts = jax.ShapeDtypeStruct((10,), jnp.int32, sharding=chip)
    gen = Generator(module, params, GenerationConfig(max_new_tokens=64, temperature=0.0))
    compiled = gen._decode.lower(params, cache, tok, lengths, done, on_chip(lambda: jax.random.PRNGKey(0)), counts, steps=8).compile()
    assert gen.decode_attention_path == "latent_paged_kernel" and gen.counter_names[-3:] == module.counters[-3:]
    assert compiled.memory_analysis().temp_size_in_bytes < slots * 32 * 128 * 128 * 4


def test_hybrid_admission_programs_compile_for_v5e(chip):
    """The think_sat cell's admission at published widths (three layers): the set-up builds a row cache whose KDA
    layers are a zero state and zero tails and whose MLA layer is a latent row of ``cache_len``; the chunk program
    takes 256 tokens through the delta rule's chunk form (the triangular solve and the sub-block products among
    them) inside half a GB of temporaries; the paste writes the latent pages and row ``slot`` of the state planes
    in one program, without re-laying the latent pool."""
    from unionml_tpu.models import GenerationConfig, Generator
    from unionml_tpu.models.generate import init_paged_cache
    from unionml_tpu.serving import ContinuousBatcher

    module, chunk, page, max_prompt, max_new = _ling(), 256, 64, 2048, 1536
    on_chip = functools.partial(_on_chip, chip)

    def scalar(dtype, shape=()):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = on_chip(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    cfg = GenerationConfig(max_new_tokens=max_new, temperature=0.0, prompt_buckets=tuple(range(chunk, max_prompt + 1, chunk)))
    gen = Generator(module, params, cfg)
    batcher = ContinuousBatcher(gen, slots=168, pool_blocks=9408, decode_chunk=8, block_size=page, admit_chunk=chunk, prefix_cache=False)
    try:
        assert batcher.cache_len == 3592 and batcher.max_blocks == 57
        lengths, key, row_valid, (last,), (row,) = on_chip(lambda: batcher._setup_fn(jnp.uint32(0), jnp.int32(0), ()))
        assert row[0]["S"].shape == (1, 32, 128, 128) and row[2]["k"].shape == (1, batcher.cache_len, 1, 640)
        step = gen._prefill_chunk.lower(params, scalar(jnp.int32, (1, chunk)), scalar(jnp.int32), lengths, row, row_valid, last).compile()
        assert step.memory_analysis().temp_size_in_bytes < 0.5e9
        pool = on_chip(lambda: init_paged_cache(
            module.config, batcher.slots, batcher.pool_blocks + 1, page, batcher.max_blocks, fill_block=batcher.pool_blocks
        ))
        carry = (scalar(jnp.int32, (batcher.slots,)), scalar(jnp.int32, (batcher.slots,)), scalar(jnp.bool_, (batcher.slots,)))
        paste = batcher._paged_admit_fn.lower(
            pool, row, *carry, scalar(jnp.int32), scalar(jnp.int32, (1,)), scalar(jnp.int32, (1,)),
            scalar(jnp.int32, (batcher.max_blocks,)), scalar(jnp.int32),
        ).compile()
        assert not re.search(rf"= bf16\[1,{batcher.pool_blocks + 1},{page},640\]\S* copy\(", paste.as_text())
        assert paste.memory_analysis().temp_size_in_bytes < (batcher.pool_blocks + 1) * page * 640 * 2
    finally:
        batcher.close()
