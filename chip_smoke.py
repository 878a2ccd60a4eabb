#!/usr/bin/env python3
"""Prove that the serving engine, the trainer and the shipped kernels run on the chip.

    python chip_smoke.py              # one TPU chip: kernels, fence, serve, decode paths, afmoe, train
    python chip_smoke.py --chips 4    # one four-chip host: TP=4 serving, four replicas, sharded fit
    python chip_smoke.py --rehearse   # tiny sizes on whatever backend there is; can never pass

Every phase prints one JSON object on its own line. The last line of a passing run
is exactly ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
a run in which any phase failed, or that found no TPU, exits non-zero and never
prints it. Nothing here pins to, retries on, or carries on with the CPU.

One process owns the chip for the whole run: the HTTP server is a thread, its
clients are threads, and no child process is started. Weights, prompts and data
are generated from ``--seed``; nothing is read from outside the checkout.
"""

from __future__ import annotations

import argparse
import asyncio
import concurrent.futures
import gc
import http.client
import json
import logging
import os
import socket
import sys
import threading
import time
import traceback
import types
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

# Tolerances, each with its reason.
#: |served - reference| per-token log-probability, nats. Both paths compute in bf16
#: (8 significant bits) and the greedy token's logit lies in [4, 8), where one bf16 ulp
#: is 2**-5 = 0.031: the cached path (chunked prefill through a dense row, paged gather
#: decode, all-reduced partial sums under TP) rounds in a different order from the
#: one-shot forward, and one chip measured exactly one ulp (0.0313-0.0315, PR 21). Four
#: ulps are allowed. A wrong cache row, position or block table moves a log-prob by
#: whole nats (the emitted token stops being the reference's near-argmax among 128k).
LOGPROB_ATOL = 0.125
#: kernels vs an f32 XLA reference: max|a-b| / max|b|. bf16 outputs carry a
#: half-ulp of 2**-9 = 0.2% of their own magnitude, the probabilities are rounded
#: to bf16 before P@V, and int8 pages add a per-row quantization step the reference
#: shares; 2% of the largest value leaves room for those and none for a wrong
#: mask, offset, head mapping or scale (those are errors of order one).
KERNEL_RTOL = 2e-2
#: sharded vs one-device training loss after the same batches: the same bf16
#: arithmetic, reduced across four chips in a different order.
FIT_LOSS_RTOL = 2e-2
#: block_until_ready vs a scalar fetch around the same chain of matmuls.
FENCE_RTOL = 0.10
#: per-device bytes under TP=4: the largest device may hold this many times the
#: smallest (replicated norms, table copies and allocator rounding are small).
TP_BYTES_SPREAD = 1.25


def sizes(rehearse: bool) -> types.SimpleNamespace:
    """The real sizes, or the tiny ones ``--rehearse`` uses to walk the control flow."""
    import jax.numpy as jnp

    if rehearse:
        return types.SimpleNamespace(
            llama=dict(
                vocab_size=512, dim=128, n_layers=2, n_heads=8, n_kv_heads=4, hidden_dim=256,
                max_seq_len=512, param_dtype=jnp.bfloat16,
            ),
            replica_layers=1, buckets=(32, 128), short=(20, 24, 28), long=100, shared_prefix=64,
            max_new=8, slots=4, block_size=16, pool_blocks=64, admit_chunk=32,
            bert=dict(vocab_size=512, dim=128, n_layers=2, n_heads=4, hidden_dim=256, max_seq_len=64),
            train_batch=8, train_seq=32, train_steps=6, learning_rate=1e-3, fit_steps=6, fit_learning_rate=1e-3,
            flash=(1, 256, 4, 2, 128), matmul=(8, 256, 512), paged=(4, 2, 2, 8, 16, 128),
            fence=(256, 8),
            afmoe=dict(
                hidden_size=128, head_dim=16, num_attention_heads=8, num_key_value_heads=4, vocab_size=512,
                intermediate_size=256, moe_intermediate_size=64, num_experts=4, router_experts=8, experts_first=2,
                num_experts_per_tok=2, sliding_window=32,
            ),
            afmoe_prompts=(100, 20, 24, 28, 60, 40), afmoe_buckets=(32, 128),
        )
    return types.SimpleNamespace(
        # LlamaConfig.llama3_8b's published widths; depth cut 32 -> 8 (2.8 B parameters,
        # 5.6 GB in bf16) so weights, pool and the reference forward share 16 GB
        llama=dict(n_layers=8, param_dtype=jnp.bfloat16, max_seq_len=4096),
        replica_layers=2, buckets=(256, 2048), short=(190, 200, 210), long=1900, shared_prefix=1000,
        max_new=64, slots=8, block_size=64, pool_blocks=512, admit_chunk=256,
        bert={},  # BertConfig.base(), uncut
        train_batch=32, train_seq=128, train_steps=20, learning_rate=5e-5,
        # the sharded comparison: fewer, smaller steps, so rounding differences stay rounding differences
        fit_steps=10, fit_learning_rate=1e-5,
        # (batch, length, heads, kv_heads, head_dim); (M, K, F); (rows, heads, kv_heads, pages/row, page, head_dim)
        flash=(4, 1024, 32, 8, 128), matmul=(8, 4096, 14336), paged=(8, 32, 8, 64, 64, 128),
        fence=(8192, 100),  # matrix side, chain length: ~110 TFLOP, most of a second
        # Trinity-Large-Preview's published widths, one of 8 expert-parallel chips' share (32 of 256 experts, an
        # eighth of the vocabulary), depth cut to a sliding and a full expert layer: 2.1 B parameters, 4.3 GB
        afmoe=dict(
            hidden_size=3072, head_dim=128, num_attention_heads=48, num_key_value_heads=8, vocab_size=25024,
            intermediate_size=12288, moe_intermediate_size=3072, num_experts=32, router_experts=256, experts_first=0,
            num_experts_per_tok=4, sliding_window=4096,
        ),
        # two requests pass the 4,096-token window (one in its prompt, one while it decodes)
        afmoe_prompts=(4200, 190, 200, 210, 1900, 4070), afmoe_buckets=(256, 2048, 4352),
    )


# --------------------------------------------------------------------------- plumbing


def emit(phase: str, **fields: Any) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


class CompileMeter:
    """Seconds spent in XLA compilation (cache reads included) and persistent-cache
    hits, from JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _on_event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class EngineErrors(logging.Handler):
    """Collects what the package logs at ERROR: the engine loop catches its own
    death and only logs it, so a phase has to look."""

    def __init__(self) -> None:
        super().__init__(level=logging.ERROR)
        self.messages: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.messages.append(record.getMessage())


def memory(device: Any) -> Dict[str, int]:
    stats = device.memory_stats() or {}
    return {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit") if k in stats}


def rel_err(out: Any, ref: Any) -> float:
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(out - ref)) / max(float(np.max(np.abs(ref))), 1e-30))


def check(condition: bool, message: str) -> None:
    if not condition:
        raise AssertionError(message)


# --------------------------------------------------------------------------- kernels


def phase_kernels(sz: types.SimpleNamespace, seed: int, interpret: bool) -> Dict[str, Any]:
    """Each shipped Pallas kernel, compiled and executed against an f32 XLA reference."""
    import jax
    import jax.numpy as jnp

    from unionml_tpu.models.layers import quantize_kv_rows
    from unionml_tpu.ops.attention import dot_product_attention, multihead_attention
    from unionml_tpu.ops.flash_attention import flash_attention
    from unionml_tpu.ops.int8_matmul import int8_matmul
    from unionml_tpu.ops.paged_attention import paged_decode_attention

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    f32 = lambda *xs: [x.astype(jnp.float32) for x in xs]  # noqa: E731
    out: Dict[str, Any] = {}

    batch, length, heads, kv_heads, dim = sz.flash
    q = jax.random.normal(next(keys), (batch, length, heads, dim), jnp.bfloat16)
    k = jax.random.normal(next(keys), (batch, length, kv_heads, dim), jnp.bfloat16)
    v = jax.random.normal(next(keys), (batch, length, kv_heads, dim), jnp.bfloat16)
    cotangent = jax.random.normal(next(keys), q.shape, jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=interpret)

    def reference(q, k, v):
        return dot_product_attention(q, k, v, causal=True)

    def out_and_grads(attend):
        def run(q, k, v, cotangent):  # an argument: a 64 MB closure constant would be compiled in
            result, vjp = jax.vjp(attend, q, k, v)
            return (result, *vjp(cotangent.astype(result.dtype)))

        return jax.jit(run)

    with jax.default_matmul_precision("highest"):  # the f32 reference must not take bf16 passes
        wanted = out_and_grads(reference)(*f32(q, k, v), cotangent)
    names = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dk", "flash_bwd_dv")
    for name, got, want in zip(names, out_and_grads(flash)(q, k, v, cotangent), wanted):
        out[name] = rel_err(got, want)

    m, k_dim, f_dim = sz.matmul
    x = jax.random.normal(next(keys), (m, k_dim), jnp.bfloat16)
    wq = jax.random.randint(next(keys), (k_dim, f_dim), -127, 128).astype(jnp.int8)
    scale = jax.random.uniform(next(keys), (1, f_dim), jnp.float32, 0.5, 1.5) / 127.0
    with jax.default_matmul_precision("highest"):
        ref_mm = jax.jit(lambda x, wq, scale: x.astype(jnp.float32) @ (wq.astype(jnp.float32) * scale))(x, wq, scale)
    out["int8_matmul"] = rel_err(jax.jit(lambda *a: int8_matmul(*a, interpret=interpret))(x, wq, scale), ref_mm)

    rows, heads, kv_heads, pages_per_row, page, dim = sz.paged
    n_pages = rows * pages_per_row
    pq = jax.random.normal(next(keys), (rows, heads, dim), jnp.bfloat16)
    k_pages = jax.random.normal(next(keys), (kv_heads, n_pages, page, dim), jnp.bfloat16)
    v_pages = jax.random.normal(next(keys), (kv_heads, n_pages, page, dim), jnp.bfloat16)
    table = jax.random.permutation(next(keys), n_pages).astype(jnp.int32).reshape(rows, pages_per_row)
    capacity = pages_per_row * page
    lengths = jnp.asarray(([capacity, 1, page, page + 1, capacity // 2 + 3] * rows)[:rows], jnp.int32)

    def gather_path(pq, k_pages, v_pages, lengths, table):
        """models/layers.py ``_paged_cached_attention``'s portable read: pool[:, table]
        back to the logical layout, masked to each row's length."""

        def logical(pool):
            rows_ = pool[:, table]  # [H_kv, B, pages, page, D]
            return jnp.transpose(rows_.reshape(rows_.shape[0], rows_.shape[1], -1, dim), (1, 2, 0, 3))

        visible = jnp.arange(capacity)[None, None, None, :] < lengths[:, None, None, None]
        return multihead_attention(
            pq[:, None], logical(k_pages), logical(v_pages), causal=False, mask=visible, impl="xla"
        )[:, 0]

    if interpret:
        out["paged_decode"] = out["paged_decode_int8"] = "not run: the library kernel has no interpret mode"
        return judged(out)
    with jax.default_matmul_precision("highest"):
        ref_paged = jax.jit(gather_path)(*f32(pq, k_pages, v_pages), lengths, table)
    out["paged_decode"] = rel_err(jax.jit(paged_decode_attention)(pq, k_pages, v_pages, lengths, table), ref_paged)
    (kq, k_scale), (vq, v_scale) = quantize_kv_rows(k_pages), quantize_kv_rows(v_pages)
    with jax.default_matmul_precision("highest"):
        ref_int8 = jax.jit(gather_path)(
            pq.astype(jnp.float32), kq.astype(jnp.float32) * k_scale, vq.astype(jnp.float32) * v_scale, lengths, table
        )
    got = jax.jit(lambda *a: paged_decode_attention(*a[:5], k_scales=a[5], v_scales=a[6]))(
        pq, kq, vq, lengths, table, k_scale, v_scale
    )
    out["paged_decode_int8"] = rel_err(got, ref_int8)
    return judged(out)


def judged(errors: Dict[str, Any]) -> Dict[str, Any]:
    bad = {name: err for name, err in errors.items() if isinstance(err, float) and not err <= KERNEL_RTOL}
    check(not bad, f"kernels off their reference by more than {KERNEL_RTOL}: {bad}")
    return {"max_abs_err_over_max_abs_ref": errors, "tolerance": KERNEL_RTOL}


# --------------------------------------------------------------------------- fence


def phase_fence(sz: types.SimpleNamespace, seed: int, judge: bool) -> Dict[str, Any]:
    """One long chain of large matmuls, timed to ``jax.block_until_ready`` and to a
    scalar fetch: both must wait for the device, and for the same time (``judge`` is
    off in a rehearsal, whose chain is too short to time)."""
    import jax
    import jax.numpy as jnp

    side, chain = sz.fence
    w = jax.random.normal(jax.random.PRNGKey(seed), (side, side), jnp.bfloat16) * side**-0.5
    x = jnp.ones((side, side), jnp.bfloat16)

    @jax.jit
    def run(x, w):
        return jax.lax.scan(lambda x, _: (jnp.tanh(x @ w), None), x, None, length=chain)[0]

    float(run(x, w)[0, 0])  # compile, and drain the queue
    readings: Dict[str, List[float]] = {"dispatch_s": [], "block_until_ready_s": [], "fetch_s": []}
    for _ in range(3):
        start = time.perf_counter()
        y = run(x, w)
        readings["dispatch_s"].append(time.perf_counter() - start)
        jax.block_until_ready(y)
        readings["block_until_ready_s"].append(time.perf_counter() - start)
        start = time.perf_counter()
        float(run(x, w)[0, 0])
        readings["fetch_s"].append(time.perf_counter() - start)
    out: Dict[str, Any] = {name: float(np.median(values)) for name, values in readings.items()}
    out["tflop"] = 2 * side**3 * chain / 1e12
    out["agree"] = abs(out["block_until_ready_s"] - out["fetch_s"]) <= FENCE_RTOL * out["fetch_s"]
    if judge:
        check(out["dispatch_s"] < 0.5 * out["fetch_s"], f"the chain did not run asynchronously: {out}")
        check(out["agree"], f"block_until_ready and a scalar fetch disagree: {out}")
    return out


# --------------------------------------------------------------------------- serve


def make_prompts(sz: types.SimpleNamespace, vocab: int, seed: int) -> Tuple[List[List[int]], List[List[int]]]:
    """Two waves of token-id prompts. The first wave's long prompt and the second
    wave's share a prefix, so the second is a radix-cache hit by construction."""
    rng = np.random.default_rng(seed)
    draw = lambda n: [int(t) for t in rng.integers(1, vocab, size=n)]  # noqa: E731
    shared = draw(sz.shared_prefix)
    tail = sz.buckets[-1] - sz.shared_prefix
    first = [shared + draw(tail - 24), draw(sz.short[0]), draw(sz.short[1]), draw(sz.long)]
    second = [shared + draw(tail - 48), draw(sz.short[2])]
    return first, second


def make_decoder(sz: types.SimpleNamespace, seed: int, **overrides: Any) -> Tuple[Any, Any, Any]:
    """Llama-3-8B widths at the smoke's depth, with seeded random bf16 weights."""
    import jax
    import jax.numpy as jnp

    from unionml_tpu.models import Llama, LlamaConfig

    config = LlamaConfig.llama3_8b(**{**sz.llama, **overrides})
    module = Llama(config)
    params = jax.jit(lambda key: module.init(key, jnp.zeros((1, 8), jnp.int32))["params"])(jax.random.PRNGKey(seed))
    return config, module, params


def generation_config(sz: types.SimpleNamespace, buckets: Tuple[int, ...]) -> Any:
    from unionml_tpu.models import GenerationConfig

    return GenerationConfig(max_new_tokens=sz.max_new, temperature=0.0, prompt_buckets=buckets)


def engine_options(sz: types.SimpleNamespace, pool_blocks: int) -> Dict[str, Any]:
    """Paged KV, radix prefix cache and chunked admission: the engine as deployments run it."""
    return dict(
        slots=sz.slots, decode_chunk=8, block_size=sz.block_size, pool_blocks=pool_blocks,
        admit_chunk=sz.admit_chunk, prefix_cache=True,
    )


def make_engine(module: Any, params: Any, sz: types.SimpleNamespace, mesh: Any = None) -> Tuple[Any, Any, float]:
    """Generator + ContinuousBatcher over ``params`` (tensor-parallel over ``mesh``),
    warmed; returns them with the seconds ``warmup()`` took."""
    from unionml_tpu.models import Generator, llama_partition_rules
    from unionml_tpu.serving import ContinuousBatcher

    rules = llama_partition_rules() if mesh is not None else None
    gen = Generator(module, params, generation_config(sz, sz.buckets), mesh=mesh, partition_rules=rules)
    batcher = ContinuousBatcher(gen, **engine_options(sz, sz.pool_blocks))
    start = time.perf_counter()
    batcher.warmup()
    return gen, batcher, time.perf_counter() - start


def logprob_diffs(module: Any, params: Any, sz: types.SimpleNamespace, prompts, answers) -> List[float]:
    """Per request, the largest |served - reference| log-probability. The reference is a
    plain, uncached ``module.apply`` over prompt+completion, teacher-forced: the
    log-softmax at each position that emitted a token, read at that token."""
    import jax
    import jax.numpy as jnp

    width = -(-(sz.buckets[-1] + sz.max_new) // 128) * 128  # one shape, one compile

    @jax.jit
    def forward(params, tokens, positions, targets):
        logits = module.apply({"params": params}, tokens)  # [1, width, vocab]
        rows = jax.nn.log_softmax(logits[0, positions].astype(jnp.float32), axis=-1)
        return jnp.take_along_axis(rows, targets[:, None], axis=1)[:, 0]

    worst = []
    for prompt, (completion, served) in zip(prompts, answers):
        check(len(served) == len(completion) == sz.max_new, f"{len(completion)} tokens, {len(served)} log-probs")
        check(bool(np.all(np.isfinite(served))), "non-finite served log-prob")
        tokens = np.zeros((1, width), np.int32)  # right padding: causal attention never sees it
        tokens[0, : len(prompt) + len(completion)] = prompt + completion
        positions = np.arange(len(completion), dtype=np.int32) + len(prompt) - 1
        reference = forward(params, tokens, positions, np.asarray(completion, np.int32))
        worst.append(float(np.max(np.abs(np.asarray(served, np.float32) - np.asarray(reference)))))
    check(max(worst) <= LOGPROB_ATOL, f"served log-probs off the plain forward by {worst} nats (> {LOGPROB_ATOL})")
    return worst


def request(port: int, method: str, path: str, payload: Any = None) -> bytes:
    """One request to the loopback server; anything but a 200 fails the phase."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        body = None if payload is None else json.dumps(payload)
        conn.request(method, path, body=body, headers={"Content-Type": "application/json"})
        response = conn.getresponse()
        answer = response.read()
        check(response.status == 200, f"{method} {path}: HTTP {response.status}: {answer[:300]!r}")
        return answer
    finally:
        conn.close()


def find_key(tree: Any, key: str) -> List[Any]:
    """Every value stored under ``key`` anywhere in a JSON tree."""
    if isinstance(tree, dict):
        return [v for k, v in tree.items() if k == key] + [x for v in tree.values() for x in find_key(v, key)]
    if isinstance(tree, list):
        return [x for v in tree for x in find_key(v, key)]
    return []


class Server:
    """``model.serve()`` on a loopback port, in a thread of this process."""

    def __init__(self, app: Any) -> None:
        self.app = app
        with socket.socket() as probe:
            probe.bind(("127.0.0.1", 0))
            self.port = probe.getsockname()[1]
        self.loop = asyncio.new_event_loop()
        self.error: List[BaseException] = []
        self.thread = threading.Thread(target=self._run, name="chip-smoke-http", daemon=True)

    def _run(self) -> None:
        try:
            self.app.startup()
            self.loop.run_until_complete(self.app.server.serve("127.0.0.1", self.port))
        except BaseException as exc:  # reported by whoever waits on the thread
            self.error.append(exc)
        finally:
            self.loop.close()

    def __enter__(self) -> "Server":
        self.thread.start()
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and self.thread.is_alive():
            try:
                socket.create_connection(("127.0.0.1", self.port), timeout=1).close()
                return self
            except OSError:
                time.sleep(0.05)
        raise RuntimeError(f"server did not come up: {self.error}")

    def __exit__(self, *exc: Any) -> None:
        if self.thread.is_alive():
            asyncio.run_coroutine_threadsafe(self.app.server.shutdown(30.0), self.loop).result(timeout=60)
        self.thread.join(timeout=60)
        check(not self.thread.is_alive(), "HTTP server thread did not stop")
        if self.error and exc[0] is None:
            raise self.error[0]


def phase_serve(sz: types.SimpleNamespace, seed: int, errors: EngineErrors) -> Dict[str, Any]:
    """HTTP front -> ContinuousBatcher -> Generator -> paged KV, radix cache and chunked
    admission on, at Llama-3-8B widths; served log-probs against the plain forward."""
    import jax
    import jax.numpy as jnp

    from unionml_tpu import Dataset, Model
    from unionml_tpu.model import ModelArtifact

    device = jax.devices()[0]
    config, module, params = make_decoder(sz, seed)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    _, batcher, warmup_s = make_engine(module, params, sz)

    dataset = Dataset(name="token_prompts")
    model = Model(name="chip-smoke-llama", dataset=dataset)
    model.generation_batcher = batcher  # /v1/completions and /metrics read the engine from here

    @dataset.reader
    def reader() -> list:
        return []

    @dataset.feature_loader
    def feature_loader(raw: list) -> list:  # prompts are token-id lists, not tabular records
        return raw

    @model.stream_predictor
    def stream_predictor(model_object: Any, prompts: list):
        for chunk in batcher.submit([int(t) for t in prompts[0]]):
            yield [[int(t) for t in chunk]]

    model.artifact = ModelArtifact(params)
    first, second = make_prompts(sz, config.vocab_size, seed)

    def complete(port: int, prompt: List[int]) -> Tuple[List[int], List[float]]:
        body = request(port, "POST", "/v1/completions", {"prompt": prompt, "max_tokens": sz.max_new, "logprobs": 1})
        choice = json.loads(body)["choices"][0]
        return [int(t) for t in choice["logprobs"]["tokens"]], choice["logprobs"]["token_logprobs"]

    def stream(port: int, prompt: List[int]) -> List[int]:
        body = request(port, "POST", "/predict-stream", {"features": [prompt]})
        return [t for line in body.decode().splitlines() for t in json.loads(line)[0]]

    with Server(model.serve()) as server, concurrent.futures.ThreadPoolExecutor(8) as pool:
        answers = list(pool.map(lambda p: complete(server.port, p), first))
        streamed = pool.submit(stream, server.port, first[1])
        answers += list(pool.map(lambda p: complete(server.port, p), second))
        streamed_tokens = streamed.result()
        metrics = json.loads(request(server.port, "GET", "/metrics"))
    batcher.close()

    check(len(streamed_tokens) == sz.max_new, f"/predict-stream gave {len(streamed_tokens)} tokens")
    check(all(0 <= t < config.vocab_size for t in streamed_tokens), "streamed token outside the vocabulary")
    generation = metrics["generation"]
    requests = len(first) + len(second) + 1
    check(generation["ttft_ms"].get("window") == requests, f"admissions on /metrics: {generation['ttft_ms']}")
    check(generation["prefill"]["mode"] == "chunked" and generation["prefill"]["chunks"] > 0, "no prefill chunk ran")
    check(generation["prefix_cache"]["hits"] >= 1, f"no radix-cache hit: {generation['prefix_cache']}")
    check(not generation["draining"], "the engine closed itself while serving")
    check(True not in find_key(metrics, "eager_fallback"), "a predictor fell back to eager execution")
    check(not errors.messages, f"the package logged errors: {errors.messages}")

    worst = logprob_diffs(module, params, sz, first + second, answers)
    return {
        "model": f"llama3-8b widths, {config.n_layers} layers, {n_params / 1e9:.2f} B parameters, bf16",
        "warmup_s": round(warmup_s, 1),
        "requests": requests,
        "prompt_tokens": [len(p) for p in first + second],
        "logprob_max_abs_diff": [round(w, 4) for w in worst],
        "prefill_chunks": generation["prefill"]["chunks"],
        "prefix_cache": {k: generation["prefix_cache"][k] for k in ("hits", "misses", "tokens_avoided")},
        "decode_dispatches": generation["decode_dispatches"],
        "ttft_ms": generation["ttft_ms"],
        "memory": memory(device),
    }


def phase_decode_paths(sz: types.SimpleNamespace, seed: int, errors: EngineErrors) -> Dict[str, Any]:
    """The two reads of a paged cache against each other, through the engine: the same
    requests with ``attention_impl="auto"`` (on a TPU the paged-attention kernel) and
    ``"xla"`` (the gather), greedy, at the serve phase's widths. Per request the served
    log-probabilities are compared over the tokens both engines emitted alike (a greedy
    near-tie may part two bf16 paths; what follows it is another sequence)."""
    import dataclasses

    import jax

    from unionml_tpu.models import Llama

    config, module, params = make_decoder(sz, seed)
    first, second = make_prompts(sz, config.vocab_size, seed)
    prompts = first + second
    served: Dict[str, types.SimpleNamespace] = {}
    for impl in ("auto", "xla"):
        _, batcher, _ = make_engine(Llama(dataclasses.replace(config, attention_impl=impl)), params, sz)
        streams = [batcher.submit(p, logprobs=True) for p in prompts]
        tokens = [[int(t) for chunk in stream for t in np.asarray(chunk).ravel()] for stream in streams]
        served[impl] = types.SimpleNamespace(
            tokens=tokens, logprobs=[list(stream.logprobs) for stream in streams], path=batcher.stats()["decode_attention_path"]
        )
        batcher.close()
        gc.collect()
    check(not errors.messages, f"the package logged errors: {errors.messages}")

    auto, xla = served["auto"], served["xla"]
    on_tpu = jax.default_backend() == "tpu"
    check(auto.path == ("paged_kernel" if on_tpu else "gather"), f"impl='auto' decoded through {auto.path}")
    check(xla.path == "gather", f"impl='xla' decoded through {xla.path}")
    alike, worst = [], []
    for a_tokens, a_lps, x_tokens, x_lps in zip(auto.tokens, auto.logprobs, xla.tokens, xla.logprobs):
        check(len(a_tokens) == len(x_tokens) == len(a_lps) == len(x_lps) == sz.max_new, "a request came back short")
        same = next((i for i, (a, x) in enumerate(zip(a_tokens, x_tokens)) if a != x), sz.max_new)
        alike.append(same)
        worst.append(float(np.max(np.abs(np.asarray(a_lps[:same]) - np.asarray(x_lps[:same])))) if same else 0.0)
    check(max(worst) <= LOGPROB_ATOL, f"the two decode reads differ by {worst} nats (> {LOGPROB_ATOL})")
    check(2 * sum(alike) >= sz.max_new * len(prompts), f"the two decode reads part early: {alike} of {sz.max_new} tokens alike")
    return {
        "paths": {impl: served[impl].path for impl in served},
        "requests": len(prompts),
        "tokens_alike": alike,
        "logprob_max_abs_diff": [round(w, 4) for w in worst],
    }


def phase_afmoe(sz: types.SimpleNamespace, seed: int, errors: EngineErrors) -> Dict[str, Any]:
    """Six requests through ``AfmoeTransformer`` (a sliding and a full expert layer at the published widths, one
    chip's share of the experts) on the engine as deployments run it, against the benchmark's plain float32
    reference given the same share and the same weights. A routed layer's comparison is heavy-tailed (a choice
    of expert that lay within the bfloat16 stream's rounding moves that token by tenths of a nat), so the
    middle of the distribution is held to the rounding tolerance and nine tokens in ten to half a nat; a wrong
    window, page or expert moves every later token by whole nats."""
    import jax

    from perf.reference import afmoe_decoder as reference
    from perf.systems.afmoe_serving import module_config
    from unionml_tpu.models import AfmoeTransformer, Generator
    from unionml_tpu.serving import ContinuousBatcher

    cfg = dict(
        sz.afmoe, num_hidden_layers=2, num_dense_layers=0, layer_types=["sliding_attention", "full_attention"],
        num_shared_experts=1, rope_theta=10000.0, rms_norm_eps=1e-5, score_func="sigmoid", route_norm=True,
        route_scale=2.448, mup_enabled=True, max_position_embeddings=8192, precision={"compute_dtype": "bfloat16"},
    )
    weights = reference.make_weights(cfg, seed)
    gen = Generator(AfmoeTransformer(module_config(cfg)), weights, generation_config(sz, sz.afmoe_buckets))
    blocks = sum(-(-(n + sz.max_new) // sz.block_size) for n in sz.afmoe_prompts) + sz.slots
    batcher = ContinuousBatcher(gen, **engine_options(sz, blocks))
    batcher.warmup()
    rng = np.random.default_rng(seed)
    prompts = [[int(t) for t in rng.integers(1, cfg["vocab_size"], size=n)] for n in sz.afmoe_prompts]
    streams = [batcher.submit(p, logprobs=True) for p in prompts]
    served = [[int(t) for chunk in stream for t in np.asarray(chunk).ravel()] for stream in streams]
    stats = batcher.stats()
    batcher.close()
    del gen, batcher
    gc.collect()
    check(not errors.messages, f"the package logged errors: {errors.messages}")

    on_tpu = jax.default_backend() == "tpu"
    diffs: List[float] = []
    for prompt, tokens, stream in zip(prompts, served, streams):
        check(len(tokens) == len(stream.logprobs) == sz.max_new, f"{len(tokens)} tokens, {len(stream.logprobs)} log-probs")
        rows = [len(prompt) - 1 + i for i in range(len(tokens))]
        logits = reference.logits_at(weights, cfg, prompt + tokens[:-1], rows, pad_to=sz.afmoe_buckets[0])
        reference_lp = jax.nn.log_softmax(logits, axis=-1)[np.arange(len(tokens)), np.asarray(tokens)]
        diffs.extend(np.abs(np.asarray(stream.logprobs, np.float32) - np.asarray(reference_lp)).tolist())
    median, within = float(np.median(diffs)), float(np.mean(np.asarray(diffs) <= 0.5))
    check(median <= LOGPROB_ATOL / 2, f"served log-probs off the reference by {median} nats at the median (> {LOGPROB_ATOL / 2})")
    check(within >= 0.9, f"only {within:.0%} of the served log-probs lie within half a nat of the reference")
    moe = stats["moe"]
    check(moe["routed_pairs"] > moe["local_pairs"] > 0 and moe["decode"]["experts_hit"] > 0, f"routing counters {moe}")
    check(stats["decode_attention_path"] == ("paged_kernel" if on_tpu else "gather"), f"decoded through {stats['decode_attention_path']}")
    # the kernel read of the sliding layer starts at the window's first page; the gather read masks instead
    check((stats["decode_window_pages_skipped"] > 0) == on_tpu, f"window pages skipped: {stats['decode_window_pages_skipped']}")
    return {
        "requests": len(prompts), "prompt_tokens": list(sz.afmoe_prompts), "logprob_abs_diff_median": round(median, 5),
        "logprob_abs_diff_max": round(max(diffs), 4), "within_half_a_nat": round(within, 4), "moe": moe,
        "decode_attention_path": stats["decode_attention_path"], "decode_window_pages_skipped": stats["decode_window_pages_skipped"],
    }


# --------------------------------------------------------------------------- train


def bert_task(sz: types.SimpleNamespace, seed: int, learning_rate: float):
    """BERT-base, its seeded initial state, the canonical train step, and batches."""
    import jax
    import jax.numpy as jnp
    import optax
    from flax.training import train_state

    from unionml_tpu import make_train_step
    from unionml_tpu.models import BertConfig, BertEncoder, classification_loss

    config = BertConfig.base(**sz.bert)
    module = BertEncoder(config)

    def init_state() -> Any:
        params = module.init(jax.random.PRNGKey(seed), jnp.zeros((1, sz.train_seq), jnp.int32))["params"]
        return train_state.TrainState.create(
            apply_fn=module.apply, params=params, tx=optax.adamw(learning_rate, weight_decay=0.01)
        )

    step = make_train_step(
        lambda p, batch: classification_loss(lambda pp, t: module.apply({"params": pp}, t), p, batch), has_aux=True
    )

    def batches(n: int, repeat: bool) -> np.ndarray:
        """``[n * batch, seq + 1]`` int32: tokens, then the label in the last column."""
        rng = np.random.default_rng(seed)
        rows = sz.train_batch if repeat else n * sz.train_batch
        data = rng.integers(0, config.vocab_size, size=(rows, sz.train_seq + 1), dtype=np.int32)
        data[:, -1] = rng.integers(0, config.num_classes, size=rows)
        return np.tile(data, (n, 1)) if repeat else data

    return config, init_state, step, batches


def phase_train(sz: types.SimpleNamespace, seed: int) -> Dict[str, Any]:
    """``Model.train`` -> ``train.fit`` in step mode on BERT-base, one batch repeated."""
    import jax

    from unionml_tpu import Dataset, Model, TrainerConfig

    device = jax.devices()[0]
    before = memory(device)
    config, init_state, step, batches = bert_task(sz, seed, sz.learning_rate)
    dataset = Dataset(name="sst2_shaped")
    model = Model(name="chip-smoke-bert", dataset=dataset)

    @dataset.reader
    def reader(steps: int) -> np.ndarray:
        return batches(steps, repeat=True)

    @dataset.parser
    def parser(data: np.ndarray, features: Optional[List[str]], targets: List[str]) -> Tuple[np.ndarray, np.ndarray]:
        return data[:, :-1], data[:, -1]

    @model.init
    def init(hyperparameters: dict) -> Any:
        return init_state()

    @model.trainer(config=TrainerConfig(epochs=1, batch_size=sz.train_batch, shuffle=False, log_every_steps=1))
    def trainer(state: Any, batch: Any) -> tuple:
        return step(state, batch)

    model.train(steps=sz.train_steps)
    result = model.last_fit_result
    check(result is not None and result.steps == sz.train_steps, f"fit ran {getattr(result, 'steps', None)} steps")
    losses = [float(entry["loss"]) for entry in result.history]
    check(len(losses) == sz.train_steps and bool(np.all(np.isfinite(losses))), f"losses: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall on a repeated batch: {losses[0]} -> {losses[-1]}")
    return {
        "model": f"bert-base widths ({config.n_layers} layers, dim {config.dim}), batch {sz.train_batch} x {sz.train_seq}",
        "steps": result.steps,
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        "first_step_s": round(result.compile_time_s, 1),
        "samples_per_s": round(result.samples_per_sec, 1),
        "memory_before": before,
        "memory": result.memory_stats,
    }


# --------------------------------------------------------------------------- four chips


def shard_bytes(arrays: Any) -> List[int]:
    """Bytes of the given arrays' shards on each device, in ``jax.devices()`` order."""
    import jax

    held = dict.fromkeys(jax.devices(), 0)
    for array in jax.tree_util.tree_leaves(arrays):
        for shard in array.addressable_shards:
            held[shard.device] += shard.data.nbytes
    return list(held.values())


def per_device_bytes() -> List[int]:
    """Bytes each device holds: the allocator's count (arrays and loaded programs),
    or where the backend keeps none (a CPU rehearsal) the live arrays' shards."""
    import jax

    devices = jax.devices()
    if devices[0].memory_stats():
        return [memory(d)["bytes_in_use"] for d in devices]
    return shard_bytes(jax.live_arrays())


def phase_tp_serve(sz: types.SimpleNamespace, seed: int, errors: EngineErrors) -> Dict[str, Any]:
    """The serve phase's decoder sharded ``MeshSpec(model=4)`` through Generator +
    ContinuousBatcher; log-probs against the unsharded plain forward, bytes per device."""
    import jax

    from unionml_tpu import MeshSpec

    config, module, unsharded = make_decoder(sz, seed)
    weight_bytes = sum(p.nbytes for p in jax.tree_util.tree_leaves(unsharded))
    mesh = MeshSpec(data=1, model=len(jax.devices())).build()
    gen, batcher, warmup_s = make_engine(module, unsharded, sz, mesh)

    first, second = make_prompts(sz, config.vocab_size, seed)
    answers = []
    for wave in (first, second):  # submit a whole wave, then drain it: the engine batches them
        streams = [batcher.submit(prompt, logprobs=True) for prompt in wave]
        answers += [([int(t) for chunk in stream for t in chunk], list(stream.logprobs)) for stream in streams]
    stats = batcher.stats()
    check(stats["prefix_cache"]["hits"] >= 1, f"no radix-cache hit: {stats['prefix_cache']}")
    check(not errors.messages, f"the package logged errors: {errors.messages}")
    worst = logprob_diffs(module, unsharded, sz, first + second, answers)

    # the engine's own decode program, as compiled for the mesh, must hold the
    # tensor-parallel all-reduces
    carry = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), batcher._carry
    )
    hlo = gen._decode.lower(gen.params, *carry, steps=batcher.decode_chunk).compile().as_text()
    all_reduces = hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")
    check(all_reduces > 0, "the sharded decode program holds no all-reduce")

    # with the unsharded copy gone, each chip holds its quarter of weights + pool
    del unsharded, carry
    gc.collect()
    used = per_device_bytes()
    pool_bytes = stats["kv_blocks"]["total"] * stats["kv_blocks"]["block_bytes"]
    share = (weight_bytes + pool_bytes) / len(used)
    check(max(used) <= TP_BYTES_SPREAD * min(used), f"per-device bytes uneven: {used}")
    check(0.9 * share <= min(used) and max(used) <= 1.5 * share, f"per-device bytes {used} vs a share of {share:.3g}")
    batcher.close()
    return {
        "mesh": {name: int(size) for name, size in mesh.shape.items() if size > 1},
        "warmup_s": round(warmup_s, 1),
        "logprob_max_abs_diff": [round(w, 4) for w in worst],
        "prefix_cache_hits": stats["prefix_cache"]["hits"],
        "decode_all_reduces": all_reduces,
        "bytes_in_use_per_device": used,
        "quarter_of_weights_and_pool": int(share),
    }


def phase_replicas(sz: types.SimpleNamespace, seed: int, errors: EngineErrors) -> Dict[str, Any]:
    """Four one-chip replicas behind the router (``serve --dp-replicas 4``): eight
    requests, every replica on its own device, every replica used."""
    import jax

    from unionml_tpu.serving.replicas import ReplicaSet

    config, module, params = make_decoder(sz, seed, n_layers=sz.replica_layers)
    n = len(jax.devices())
    fleet = ReplicaSet.build(
        module, params, generation_config(sz, sz.buckets[:1]), replicas=n, **engine_options(sz, sz.pool_blocks // n)
    )
    start = time.perf_counter()
    fleet.warmup()
    warmup_s = time.perf_counter() - start
    homes = [sorted(d.id for d in batcher.gen.mesh.devices.flat) for batcher in fleet.batchers]
    check(len({tuple(h) for h in homes}) == n and all(len(h) == 1 for h in homes), f"replica devices: {homes}")

    rng = np.random.default_rng(seed)
    prompts = [[int(t) for t in rng.integers(1, config.vocab_size, size=sz.short[0])] for _ in range(n)] * 2
    streams = [fleet.submit(prompt, logprobs=True) for prompt in prompts]  # all in flight at once
    answers = []
    for stream in streams:
        tokens = [int(t) for chunk in stream for t in chunk]
        check(len(tokens) == sz.max_new, f"asked for {sz.max_new} tokens, got {len(tokens)}")
        answers.append(list(stream.logprobs))
    submitted = find_key(fleet.stats(), "submitted")[0]
    check(len(submitted) == n and min(submitted) >= 1, f"requests per replica: {submitted}")
    # the same prompt twice, wherever each copy was routed: identical weights on identical chips
    twin_diff = max(float(np.max(np.abs(np.subtract(a, b)))) for a, b in zip(answers[:n], answers[n:]))
    check(twin_diff <= LOGPROB_ATOL, f"the same prompt differed by {twin_diff} nats between replicas")
    check(not errors.messages, f"the package logged errors: {errors.messages}")
    used = per_device_bytes()
    fleet.close()
    return {
        "replicas": n, "layers": config.n_layers, "warmup_s": round(warmup_s, 1), "replica_device_ids": homes,
        "requests_per_replica": submitted, "twin_logprob_max_abs_diff": round(twin_diff, 4),
        "bytes_in_use_per_device": used,
    }


def phase_sharded_fit(sz: types.SimpleNamespace, seed: int) -> Dict[str, Any]:
    """``fit`` on BERT-base under ``MeshSpec(fsdp=4)`` against a plain one-device loop
    over the same seed and batches."""
    import jax

    from unionml_tpu import MeshSpec, TrainerConfig
    from unionml_tpu.train import fit

    config, init_state, step, batches = bert_task(sz, seed, sz.fit_learning_rate)
    data = batches(sz.fit_steps, repeat=False)
    tokens, labels = data[:, :-1], data[:, -1]

    state, plain, jitted = init_state(), [], jax.jit(step, donate_argnums=0)
    for i in range(sz.fit_steps):
        rows = slice(i * sz.train_batch, (i + 1) * sz.train_batch)
        state, metrics = jitted(state, (tokens[rows], labels[rows]))
        plain.append(float(metrics["loss"]))
    del state, jitted
    gc.collect()

    n = len(jax.devices())
    result = fit(
        init_state(), step, [tokens, labels],
        TrainerConfig(
            epochs=1, batch_size=sz.train_batch, shuffle=False, log_every_steps=1, mesh=MeshSpec(data=1, fsdp=n)
        ),
    )
    sharded = [float(entry["loss"]) for entry in result.history]
    check(len(sharded) == len(plain) == sz.fit_steps, f"{len(sharded)} sharded vs {len(plain)} plain steps")
    check(bool(np.all(np.isfinite(sharded))), f"sharded losses: {sharded}")
    diff = float(np.max(np.abs(np.subtract(sharded, plain)) / np.abs(plain)))
    check(diff <= FIT_LOSS_RTOL, f"fsdp={n} loss off the one-device loop by {diff} (> {FIT_LOSS_RTOL}): {sharded} vs {plain}")
    # every chip holds an equal share of the train state, and less than the whole of it
    held = shard_bytes(result.state)
    whole = sum(x.nbytes for x in jax.tree_util.tree_leaves(result.state))
    check(max(held) <= 1.01 * min(held) and max(held) < 0.6 * whole, f"train state per device {held} of {whole}")
    return {
        "mesh": {"fsdp": n}, "steps": result.steps, "loss_one_device": [round(x, 4) for x in plain],
        "loss_sharded": [round(x, 4) for x in sharded], "loss_max_rel_diff": round(diff, 5),
        "first_step_s": round(result.compile_time_s, 1), "state_bytes": whole, "state_bytes_per_device": held,
        "bytes_in_use_per_device": per_device_bytes(),
    }


# --------------------------------------------------------------------------- main


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1, help="4: the cross-chip paths, and only those")
    parser.add_argument("--seed", type=int, default=0, help="weights, prompts and data are drawn from it")
    parser.add_argument("--rehearse", action="store_true", help="tiny sizes, any backend, interpret-mode kernels; never passes")
    args = parser.parse_args()

    import jax

    from unionml_tpu import enable_compile_cache
    from unionml_tpu._logging import logger
    from unionml_tpu.native import native_available

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    emit("device", ok=on_tpu or args.rehearse, **device, memory=memory(devices[0]), native_parser=native_available())
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: no TPU (jax.devices()[0].platform == {device['platform']!r})", file=sys.stderr)
        return 2
    if len(devices) != args.chips and not args.rehearse:
        print(f"chip_smoke: --chips {args.chips} on a host with {len(devices)} devices", file=sys.stderr)
        return 2

    cache_dir = enable_compile_cache()
    if not os.access(cache_dir, os.W_OK):
        print(f"chip_smoke: compilation cache {cache_dir} is not writable", file=sys.stderr)
        return 2
    entries_before = len(os.listdir(cache_dir))
    meter = CompileMeter()
    errors = EngineErrors()
    logger.addHandler(errors)
    sz = sizes(args.rehearse)

    if args.chips == 4:
        phases: List[Tuple[str, Callable[[], Dict[str, Any]]]] = [
            ("tp_serve", lambda: phase_tp_serve(sz, args.seed, errors)),
            ("replicas", lambda: phase_replicas(sz, args.seed, errors)),
            ("sharded_fit", lambda: phase_sharded_fit(sz, args.seed)),
        ]
    else:
        phases = [
            ("kernels", lambda: phase_kernels(sz, args.seed, interpret=not on_tpu)),
            ("fence", lambda: phase_fence(sz, args.seed, judge=on_tpu)),
            ("serve", lambda: phase_serve(sz, args.seed, errors)),
            ("decode_paths", lambda: phase_decode_paths(sz, args.seed, errors)),
            ("afmoe", lambda: phase_afmoe(sz, args.seed, errors)),
            ("train", lambda: phase_train(sz, args.seed)),
        ]

    failed = []
    for name, run in phases:
        start, compiling, hits = time.perf_counter(), meter.seconds, meter.hits
        try:
            fields, ok = run(), True
        except Exception as exc:  # a failed phase is reported, the rest still run, the smoke fails
            traceback.print_exc()
            fields, ok = {"error": f"{type(exc).__name__}: {exc}"[:2000]}, False
            failed.append(name)
            errors.messages.clear()
        gc.collect()  # the next phase starts with this one's arrays freed
        emit(
            name, ok=ok, seconds=round(time.perf_counter() - start, 1),
            compile_s=round(meter.seconds - compiling, 1), cache_hits=meter.hits - hits, **fields,
        )

    entries_after = len(os.listdir(cache_dir))
    cache_ok = args.rehearse or entries_after > 0
    emit(
        "compile_cache", ok=cache_ok, directory=cache_dir, entries_before=entries_before, entries_after=entries_after,
        compile_s=round(meter.seconds, 1), cache_hits=meter.hits, peak_memory=memory(devices[0]),
    )
    if not cache_ok:
        failed.append("compile_cache")
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "device": device}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
