"""AOT program store: load-before-compile serving cold starts.

The contract under test (docs/serving.md "Cold start and AOT preload"): a
process whose store holds this topology's programs warms with ZERO fresh XLA
traces and serves tokens bit-identical to a freshly-compiled engine; stale
entries (other jax version, other mesh) and corrupted entries are *skipped* —
the engine compiles exactly as it would without the store, never crashes.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu.models import GenerationConfig, Generator, Llama, LlamaConfig
from unionml_tpu.serving import ContinuousBatcher
from unionml_tpu.serving.aot import ProgramStore, resolve_store

PROMPT = [3, 14, 15, 9, 2]


@pytest.fixture(scope="module")
def tiny():
    config = LlamaConfig.tiny(
        vocab_size=89, dim=32, n_layers=2, n_heads=2, n_kv_heads=2, hidden_dim=64,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    module = Llama(config)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return module, params


def _cfg():
    return GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(8, 16))


def _drain(stream):
    return [int(t) for chunk in stream for t in np.asarray(chunk).ravel()]


def _serve_one(module, params, tmp, **engine_kwargs):
    gen = Generator(module, params, _cfg())
    batcher = ContinuousBatcher(gen, slots=2, decode_chunk=4, aot=tmp, **engine_kwargs)
    try:
        batcher.warmup()
        tokens = _drain(batcher.submit(PROMPT))
        stats = batcher.stats()
        return gen, tokens, stats
    finally:
        batcher.close()


# ------------------------------------------------------------------ key derivation


def test_entry_key_stable_and_sensitive(tmp_path):
    store = ProgramStore(str(tmp_path))
    key = store.entry_key("prefill", {"mesh": None}, ("sig",))
    assert key == store.entry_key("prefill", {"mesh": None}, ("sig",))  # deterministic
    assert key != store.entry_key("decode", {"mesh": None}, ("sig",))  # program name
    assert key != store.entry_key("prefill", {"mesh": [0, 1]}, ("sig",))  # context
    assert key != store.entry_key("prefill", {"mesh": None}, ("other",))  # signature
    # the store-level context (jax version, backend, device ids) keys too
    other = ProgramStore(str(tmp_path))
    other._context = dict(other._context, jax="0.0.0-stale")
    assert key != other.entry_key("prefill", {"mesh": None}, ("sig",))


def test_store_meta_sidecars_record_programs(tmp_path, tiny):
    module, params = tiny
    _serve_one(module, params, str(tmp_path))
    entries = ProgramStore(str(tmp_path)).entries()
    assert entries, "warmup should have persisted entries"
    programs = {entry["program"] for entry in entries}
    assert "prefill" in programs and "decode" in programs
    for entry in entries:
        assert entry["store"]["jax"] == jax.__version__
        assert "signature" in entry and "context" in entry


# ------------------------------------------------------------------ exactness


def test_populated_store_serves_with_zero_traces_and_identical_tokens(tmp_path, tiny):
    module, params = tiny
    # reference: a plain-jit engine (no store anywhere near it)
    ref_gen = Generator(module, params, _cfg())
    ref_b = ContinuousBatcher(ref_gen, slots=2, decode_chunk=4)
    try:
        ref_b.warmup()
        ref = _drain(ref_b.submit(PROMPT))
    finally:
        ref_b.close()

    gen1, out1, stats1 = _serve_one(module, params, str(tmp_path))
    assert out1 == ref  # serialize-on-compile must not perturb the program
    assert stats1["aot"]["programs_compiled"] > 0
    assert stats1["aot"]["programs_serialized"] == stats1["aot"]["programs_compiled"]
    assert stats1["aot"]["programs_loaded"] == 0

    gen2, out2, stats2 = _serve_one(module, params, str(tmp_path))
    assert out2 == ref  # the pinned contract: AOT-loaded == freshly-compiled
    assert out2[0] == ref[0]  # first sampled token bit-identical, explicitly
    assert (gen2.prefill_traces, gen2.decode_traces) == (0, 0)  # zero fresh XLA traces
    assert stats2["aot"]["programs_compiled"] == 0
    assert stats2["aot"]["programs_loaded"] > 0
    assert stats2["aot"]["load_ms"]["window"] == stats2["aot"]["programs_loaded"]
    assert stats2["aot"]["compile_ms"] == {"window": 0}  # never a None gauge


def test_generator_warmup_preloads(tmp_path, tiny):
    module, params = tiny
    ref = Generator(module, params, _cfg())([PROMPT])
    store = ProgramStore(str(tmp_path))
    Generator(module, params, _cfg()).enable_aot(store).warmup()
    assert store.programs_compiled > 0

    store2 = ProgramStore(str(tmp_path))
    gen2 = Generator(module, params, _cfg()).enable_aot(store2).warmup()
    assert store2.programs_compiled == 0 and store2.programs_loaded > 0
    assert (gen2.prefill_traces, gen2.decode_traces) == (0, 0)
    np.testing.assert_array_equal(gen2([PROMPT]), ref)
    assert (gen2.prefill_traces, gen2.decode_traces) == (0, 0)  # the call itself hit too


@pytest.mark.parametrize("knob", ["decode_chunk", "admit_chunk"])
def test_engines_of_different_row_length_share_a_store(tmp_path, tiny, knob):
    """The admission set-ups close over the row's length, which constructor knobs raise (``decode_chunk``, a chunk
    width that pads the widest bucket) and none of their arguments' shapes shows (a seed, a length, the pool and a
    table row): it is part of their key, so a second engine on the first's store, or a restart after the knob
    changed, builds rows of its own length instead of loading the other's, cold and on a radix hit, and serves the
    tokens a plain-jit engine of the same knobs serves."""
    module, params = tiny
    long = [3, 14, 15, 9, 2, 6, 5, 3, 5, 8, 9]

    def serve(aot, **knobs):
        options = {"slots": 2, "decode_chunk": 4, "block_size": 8, "admit_chunk": 8, "prefix_cache": True, **knobs}
        batcher = ContinuousBatcher(Generator(module, params, _cfg()), aot=aot, **options)
        try:
            batcher.warmup()
            outs = [_drain(batcher.submit(p)) for p in (long, long[:9] + [4, 4])]  # a cold admission, a radix hit
            stats = batcher.stats()
            assert stats["prefix_cache"]["hits"] >= 1
        finally:
            batcher.close()
        seed, total = np.uint32(0), np.int32(5)
        *_, (cold_row,) = batcher._setup_fn(seed, total, ())
        *_, (hit_row,) = batcher._cached_setup_fn(batcher._carry[0], np.zeros(batcher.max_blocks, np.int32), seed, total)
        widths = {row[0]["k"].shape[1] for row in (cold_row, hit_row)}
        return batcher.cache_len, widths, outs, stats

    other = {"decode_chunk": 12} if knob == "decode_chunk" else {"admit_chunk": 12}
    first_len, first_widths, first_outs, _ = serve(str(tmp_path))
    second_len, second_widths, second_outs, stats = serve(str(tmp_path), **other)
    _, _, expected, _ = serve(False, **other)
    assert second_len > first_len  # the knob moved the row's length
    assert first_widths == {first_len} and second_widths == {second_len}
    assert second_outs == expected and len(second_outs[0]) == 6
    assert stats["aot"]["programs_compiled"] >= 2  # its own two set-ups at least: nothing of theirs was there to load
    # and the same engine again finds every program it needs
    again_len, again_widths, again_outs, again = serve(str(tmp_path), **other)
    assert (again_len, again_widths, again_outs) == (second_len, second_widths, second_outs)
    assert again["aot"]["programs_compiled"] == 0 and again["aot"]["programs_loaded"] > 0


# ------------------------------------------------------------------ staleness / corruption


def test_stale_jax_version_entries_are_skipped(tmp_path, tiny):
    module, params = tiny
    stale = ProgramStore(str(tmp_path))
    stale._context = dict(stale._context, jax="0.0.0-stale")
    Generator(module, params, _cfg()).enable_aot(stale).warmup()
    n_entries = stale.entry_count()
    assert n_entries > 0

    # a correctly-versioned store over the same dir must not load any of them
    fresh = ProgramStore(str(tmp_path))
    gen = Generator(module, params, _cfg()).enable_aot(fresh).warmup()
    assert fresh.programs_loaded == 0  # stale keys never resolve
    assert fresh.programs_compiled > 0  # ...so it compiled, without crashing
    assert gen.prefill_traces > 0
    assert fresh.entry_count() == n_entries * 2  # old entries orphaned, not clobbered


def test_mesh_mismatch_entries_are_skipped(tmp_path, tiny):
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 emulated devices")
    from jax.sharding import Mesh

    from unionml_tpu.parallel.mesh import AXIS_ORDER

    module, params = tiny
    shape = (1,) * len(AXIS_ORDER)

    def one_device_mesh(i):
        return Mesh(np.asarray([jax.devices()[i]]).reshape(shape), AXIS_ORDER)

    s0 = ProgramStore(str(tmp_path))
    Generator(module, params, _cfg(), mesh=one_device_mesh(0)).enable_aot(s0).warmup()
    assert s0.programs_compiled > 0

    # same program shapes, DIFFERENT device assignment: must miss, not load
    s1 = ProgramStore(str(tmp_path))
    Generator(module, params, _cfg(), mesh=one_device_mesh(1)).enable_aot(s1).warmup()
    assert s1.programs_loaded == 0
    assert s1.programs_compiled > 0


def test_corrupted_entries_fall_back_to_compile(tmp_path, tiny):
    module, params = tiny
    _, ref, _ = _serve_one(module, params, str(tmp_path))
    for name in os.listdir(tmp_path):
        if name.endswith(".aotx"):
            (tmp_path / name).write_bytes(b"not a pickled executable")

    gen, out, stats = _serve_one(module, params, str(tmp_path))
    assert out == ref  # corruption degrades to compile, identically
    assert stats["aot"]["load_failures"] > 0
    assert stats["aot"]["programs_compiled"] > 0
    assert gen.prefill_traces > 0

    # the recompile overwrote the corrupt entries: a third engine loads clean
    gen3, out3, stats3 = _serve_one(module, params, str(tmp_path))
    assert out3 == ref
    assert stats3["aot"]["load_failures"] == 0
    assert stats3["aot"]["programs_loaded"] > 0
    assert (gen3.prefill_traces, gen3.decode_traces) == (0, 0)


# ------------------------------------------------------------------ knobs / degrade


def test_unusable_store_dir_degrades_to_plain_jit(tmp_path, tiny):
    module, params = tiny
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("occupied")
    assert resolve_store(str(blocker / "sub")) is None  # warned + disabled
    gen, out, stats = _serve_one(module, params, str(blocker / "sub"))
    assert "aot" not in stats  # byte-for-byte the plain engine's stats
    assert len(out) == _cfg().max_new_tokens


def test_env_resolution(tmp_path, monkeypatch, tiny):
    from unionml_tpu.defaults import serve_aot_preload

    monkeypatch.delenv("UNIONML_TPU_AOT_PRELOAD", raising=False)
    assert serve_aot_preload() is None
    assert resolve_store(None) is None
    monkeypatch.setenv("UNIONML_TPU_AOT_PRELOAD", "0")
    assert serve_aot_preload() is None
    monkeypatch.setenv("UNIONML_TPU_AOT_PRELOAD", "1")
    assert serve_aot_preload() == "~/.cache/unionml_tpu/aot"
    monkeypatch.setenv("UNIONML_TPU_AOT_PRELOAD", str(tmp_path))
    assert serve_aot_preload() == str(tmp_path)

    # an engine built with aot=None (the default) reads the export
    module, params = tiny
    batcher = ContinuousBatcher(Generator(module, params, _cfg()), slots=1, decode_chunk=4)
    try:
        assert batcher._aot is not None
        assert batcher._aot.root == str(tmp_path)
    finally:
        batcher.close()


def test_aot_off_keeps_stats_byte_for_byte(tiny):
    module, params = tiny
    batcher = ContinuousBatcher(Generator(module, params, _cfg()), slots=1, decode_chunk=4)
    try:
        assert "aot" not in batcher.stats()
    finally:
        batcher.close()


def test_aot_stats_render_clean_prometheus(tmp_path):
    """The /metrics no-None-gauge contract: the aot section (counters +
    latency windows, populated or empty) renders as clean exposition."""
    from unionml_tpu.observability.prometheus import render

    store = ProgramStore(str(tmp_path))
    store.note_compiled(0.5)
    store.note_loaded(0.01)
    text = render({"generation": {"aot": store.stats()}})
    assert "unionml_tpu_generation_aot_programs_loaded 1" in text
    assert 'unionml_tpu_generation_aot_load{quantile="0.99"}' in text
    assert "None" not in text
    empty = render({"generation": {"aot": ProgramStore(str(tmp_path)).stats()}})
    assert "unionml_tpu_generation_aot_programs_loaded 0" in empty
    assert "None" not in empty


# ------------------------------------------------------------------ serverless


def test_serverless_scale_to_zero_takes_the_preload_path(tmp_path, tiny):
    """The acceptance pin: a scaled-from-zero container's ONE startup restores
    the generator's executables from the store — zero fresh XLA traces — and
    later invocations reuse the warmed engine without re-running startup."""
    from unionml_tpu.serving.serverless import lambda_handler

    module, params = tiny
    _serve_one(module, params, str(tmp_path))  # a previous process populated the store

    class _Server:
        async def dispatch_with_headers(self, method, path, body, headers):
            return 200, {"ok": True}, "application/json", {}

    class _Serving:
        def __init__(self):
            self._started = False
            self.server = _Server()
            self.batcher = None

        def startup(self):
            if self._started:
                return
            gen = Generator(module, params, _cfg())
            self.batcher = ContinuousBatcher(gen, slots=2, decode_chunk=4, aot=str(tmp_path))
            self.batcher.warmup()
            self._started = True

    serving = _Serving()
    handler = lambda_handler(serving)
    event = {"httpMethod": "GET", "path": "/health"}
    try:
        assert handler(event, None)["statusCode"] == 200
        gen = serving.batcher.gen
        assert (gen.prefill_traces, gen.decode_traces) == (0, 0)  # restored, not compiled
        aot = serving.batcher.stats()["aot"]
        assert aot["programs_compiled"] == 0 and aot["programs_loaded"] > 0
        assert handler(event, None)["statusCode"] == 200
        assert handler.stats == {
            "invocations": 2, "startups": 1,
            "cold_start_s": handler.stats["cold_start_s"],
        }
        assert serving.batcher.gen is gen  # the warmed engine was reused, not rebuilt
    finally:
        if serving.batcher is not None:
            serving.batcher.close()


# ------------------------------------------------------------------ elastic scale-up


def test_meshless_scale_up_reuses_store_on_revisited_device(tmp_path, tiny):
    """scale down → scale up re-places the replica on the same device; with the
    store warm the rejoining engine must not produce a single fresh trace."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 emulated devices")
    from unionml_tpu.serving import ReplicaSet

    module, params = tiny
    ref = Generator(module, params, _cfg())([PROMPT])[0]
    rs = ReplicaSet.build(
        module, params, _cfg(), mesh=None, replicas=2,
        slots=2, decode_chunk=4, aot=str(tmp_path),
    )
    try:
        rs.warmup()
        assert rs.scale_to(1) == 1
        assert rs.scale_to(2) == 2  # rejoins on the round-robin device it left
        new_engine = rs.batchers[1]
        assert (new_engine.gen.prefill_traces, new_engine.gen.decode_traces) == (0, 0)
        aot = new_engine.stats()["aot"]
        assert aot["programs_compiled"] == 0 and aot["programs_loaded"] > 0
        assert _drain(new_engine.submit(PROMPT)) == list(ref)
        assert (new_engine.gen.prefill_traces, new_engine.gen.decode_traces) == (0, 0)
        fleet = rs.stats()
        assert fleet["aot"]["programs_loaded"] > 0  # fleet-wide aggregation
    finally:
        rs.close()
