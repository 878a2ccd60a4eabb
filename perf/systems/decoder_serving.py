"""A causal decoder served through the program's normal path, under one traffic mix.

``POST /v1/completions`` on ``model.serve()`` -> ``ServingApp`` ->
``ContinuousBatcher.submit`` -> ``Generator`` (``prefill_chunk``, ``first_token``,
``decode_steps``) -> paged KV and the radix prefix cache. One process owns the
chip: the HTTP server is a thread, the load generator is a thread, no child is
started. The set-up pattern (model object, stream predictor, loopback server in a
thread) is a copy of ``chip_smoke.py``'s, which stays a smoke; nothing is
imported from it.

Everything a cell needs comes from data: the configuration's file (sizes, engine
settings, optional ``mesh`` and ``partition_rules``), the cell's file (pool,
positions a slot, what to check) and the traffic mix's file.
"""

from __future__ import annotations

import asyncio
import gc
import logging
import socket
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Mapping, Optional

import numpy as np

from perf import compare
from perf.reference import decoder as reference
from perf.traffic import client


class Server:
    """``model.serve()`` on a loopback port, in a thread of this process."""

    def __init__(self, app: Any, stream_threads: Optional[int]) -> None:
        self.app = app
        self.stream_threads = stream_threads
        with socket.socket() as probe:
            probe.bind(("127.0.0.1", 0))
            self.port = probe.getsockname()[1]
        self.loop = asyncio.new_event_loop()
        self.error: List[BaseException] = []
        self.thread = threading.Thread(target=self._run, name="perf-http", daemon=True)

    def _run(self) -> None:
        try:
            # every open stream blocks one thread of the loop's executor while it waits for the engine's next
            # emission, and the program sets no executor, so model.serve() runs min(32, cores + 4) threads. A mix
            # with more open streams than that names its pool (a departure from what a user of model.serve()
            # runs: PERF.md, section 4 (h) and Open questions); without the key the program's default stands
            if self.stream_threads:
                self.loop.set_default_executor(ThreadPoolExecutor(self.stream_threads, thread_name_prefix="perf-stream"))
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
        if self.thread.is_alive():
            raise RuntimeError("HTTP server thread did not stop")
        if self.error and exc[0] is None:
            raise self.error[0]


class EngineErrors(logging.Handler):
    """What the package logs at ERROR: the engine loop only logs its own death."""

    def __init__(self) -> None:
        super().__init__(level=logging.ERROR)
        self.messages: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.messages.append(record.getMessage()[:500])


def build_engine(cfg: Mapping[str, Any], cell: Mapping[str, Any], weights: Any, control: Optional[str]):
    """Llama module + Generator + ContinuousBatcher at the configuration's sizes."""
    import jax.numpy as jnp

    from unionml_tpu import models
    from unionml_tpu.models import GenerationConfig, Generator, Llama, LlamaConfig
    from unionml_tpu.serving import ContinuousBatcher

    engine = {**cfg["engine"], **cell["engine"]}
    chunk = int(engine["admit_chunk"])
    max_prompt = int(engine.pop("max_prompt_tokens"))
    max_new = int(engine.pop("max_new_tokens"))
    buckets = tuple(range(chunk, -(-max_prompt // chunk) * chunk + 1, chunk))
    module = Llama(LlamaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], hidden_dim=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"], rope_theta=float(cfg["rope_theta"]), param_dtype=jnp.bfloat16,
    ))
    mesh = rules = None
    if cfg.get("mesh"):
        from unionml_tpu import MeshSpec

        mesh = MeshSpec(**{"data": 1, **cfg["mesh"]}).build()
        rules = getattr(models, cfg["partition_rules"])()
    if control not in (None, "int8"):
        raise ValueError(f"unknown control precision {control!r}")
    # the lower-precision control is the program's own path: int8 weights and an int8 KV cache
    gen_cfg = GenerationConfig(max_new_tokens=max_new, temperature=0.0, prompt_buckets=buckets, kv_cache_dtype=control)
    gen = Generator(module, weights, gen_cfg, mesh=mesh, partition_rules=rules, quantize=control)
    batcher = ContinuousBatcher(gen, **engine)
    return gen, batcher


def plant_fault(gen: Any, fault: Optional[str], vocab: int) -> None:
    """Break the timed path underneath (the benchmark's own tests): every decode
    dispatch hands the engine each row's fourth token altered by one."""
    if fault is None:
        return
    if fault != "token_altered":
        raise ValueError(f"unknown fault {fault!r} for a serving cell")
    inner = gen._decode

    def broken(*args: Any, **kwargs: Any):
        toks, lps, carry = inner(*args, **kwargs)
        return toks.at[:, 3].set(1 + (toks[:, 3] % (vocab - 1))), lps, carry

    gen._decode = broken


def build_app(batcher: Any, weights: Any):
    from unionml_tpu import Dataset, Model
    from unionml_tpu.model import ModelArtifact

    dataset = Dataset(name="token_prompts")
    model = Model(name="perf-decoder", dataset=dataset)
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

    model.artifact = ModelArtifact(weights)
    return model.serve()


def _counters(batcher: Any) -> Dict[str, Any]:
    s = batcher.stats()
    flat = {
        "decode_dispatches": s["decode_dispatches"], "decoded_rows": batcher.decoded_rows,
        "prefill_chunks": s["prefill"]["chunks"], "prefill_chunk_tokens": s["prefill"]["chunk_tokens"],
        "shed": s["shed_queue_full"] + s["shed_deadline"], "resident": s["resident"], "waiting": s["waiting"],
    }
    if "kv_blocks" in s:
        flat.update(kv_used=s["kv_blocks"]["used"], kv_total=s["kv_blocks"]["total"], preemptions=s["kv_blocks"]["preemptions"])
    if "prefix_cache" in s:
        flat.update(prefix_hits=s["prefix_cache"]["hits"], prefix_misses=s["prefix_cache"]["misses"],
                    prefix_tokens_avoided=s["prefix_cache"]["tokens_avoided"], prefix_evicted_blocks=s["prefix_cache"]["evicted_blocks"])
    return flat


def _percentile(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def run(ctx: Any) -> Dict[str, Any]:
    from unionml_tpu._logging import logger

    cfg, cell, mix, args = ctx.config, ctx.cell, ctx.mix, ctx.args
    errors = EngineErrors()
    logger.addHandler(errors)
    seconds = float(args.seconds)
    ramp_s = float(mix.get("ramp_s", 0.0))
    timeout_s = float(mix.get("request_timeout_s", 120.0))
    want_logprobs = bool(mix.get("logprobs", True))

    weights = reference.make_weights(cfg, args.seed)
    gen, batcher = build_engine(cfg, cell, weights, args.control)
    batcher.warmup()
    plant_fault(gen, args.fault, cfg["vocab_size"])
    chunk = int(batcher.admit_chunk or 0)
    schedule = ctx.traffic.requests(mix, args.seed, cfg["vocab_size"], ramp_s + seconds)
    app = build_app(batcher, weights)
    records: List[client.Record] = []
    out: Dict[str, Any] = {}
    with Server(app, mix.get("stream_threads")) as server:
        warm = client.run_waves(server.port, ctx.traffic.warmup_requests(mix, cfg["vocab_size"], chunk), want_logprobs, 600.0)
        bad = [r.error or f"{len(r.tokens)} tokens" for r in warm if not r.ok]
        if bad:
            raise RuntimeError(f"warm-up requests failed: {bad[:3]}")
        gc.collect()
        gc.freeze()  # the set-up's objects never need collecting again; keeps gen-2 pauses out of the window

        load_error: List[BaseException] = []
        start = time.monotonic() + 0.2
        open_at = start + ramp_s
        close_at = open_at + seconds

        def drive() -> None:
            try:
                if mix["loop"] == "closed":
                    coro = client.closed_loop(server.port, schedule, int(mix["clients"]), close_at, want_logprobs, timeout_s, records)
                else:
                    coro = client.open_loop(server.port, schedule, start, want_logprobs, timeout_s, records)
                asyncio.run(coro)
            except BaseException as exc:  # surfaced after the join
                load_error.append(exc)

        loader = threading.Thread(target=drive, name="perf-load", daemon=True)
        loader.start()
        time.sleep(max(0.0, open_at - time.monotonic()))
        # ---- the window opens
        out["setup_s"] = ctx.process_age_s()
        compiles_before = ctx.compile_meter.count
        before = _counters(batcher)
        slice_facts = None
        if args.trace:
            offset = float(cell.get("trace_offset_s", min(2.0, seconds / 4)))
            length = min(float(cell.get("trace_seconds", 4.0)), max(seconds - offset - 0.5, 0.5))
            time.sleep(max(0.0, open_at + offset - time.monotonic()))
            s0, t0 = _counters(batcher), time.monotonic()
            ctx.start_trace()
            time.sleep(length)
            ctx.stop_trace()
            t1, s1 = time.monotonic(), _counters(batcher)
            slice_facts = {"t0": t0, "t1": t1, "before": s0, "after": s1}
        time.sleep(max(0.0, close_at - time.monotonic()))
        after = _counters(batcher)
        out["compiles_in_window"] = ctx.compile_meter.count - compiles_before
        # ---- the window is closed; requests in flight finish (latencies count the wait)
        loader.join(timeout=float(mix.get("drain_s", 90.0)) + timeout_s)
        if loader.is_alive():
            raise RuntimeError("the load generator did not finish after the window closed")
        if load_error:
            raise load_error[0]
        final = _counters(batcher)
    out["memory_peak_bytes"] = ctx.memory_peak_bytes()
    batcher.close()
    logger.removeHandler(errors)

    # ---- end-to-end metrics: all the work and all the time of the window
    sent = [r for r in records if r.request.index >= 0]
    pool_errors = [r for r in records if r.request.index < 0]
    if mix["loop"] == "closed":
        # a caller's request belongs to the window in which it completed (or, failed, in which it was sent)
        in_window = [r for r in sent if open_at <= (r.done if r.ok and r.done is not None else r.due) < close_at]
    else:
        in_window = [r for r in sent if open_at <= r.due < close_at]
    finished = [r for r in in_window if r.ok]
    failed = [r for r in in_window if not r.ok]
    tokens_in_window = sum(n for r in records for (t, n) in r.arrivals if open_at <= t < close_at)
    worst = timeout_s
    ttft = [r.ttft_s() if r.ok else worst for r in in_window]
    tpot = [r.tpot_s() if r.ok and r.tpot_s() is not None else worst for r in in_window]
    e2e = {"serve_tokens_per_s": tokens_in_window / seconds}
    if in_window:
        e2e["ttft_p95_ms"] = _percentile(ttft, 95) * 1e3
        e2e["tpot_p95_ms"] = _percentile(tpot, 95) * 1e3
        # printed on the detail line beside the metrics BENCHMARK.json names
        e2e["ttft_mean_ms"] = float(np.mean(ttft)) * 1e3
        e2e["ttft_p50_ms"] = _percentile(ttft, 50) * 1e3
        e2e["ttft_p90_ms"] = _percentile(ttft, 90) * 1e3
        e2e["tpot_p50_ms"] = _percentile(tpot, 50) * 1e3
    out["e2e"] = e2e
    out["attempted"] = len(in_window) + len(pool_errors)
    out["failed"] = len(failed) + len(pool_errors)
    lags = [r.sent - r.due for r in sent if r.sent]
    out["early"] = {
        "generator_lag": client.lag_summary(lags), "requests_sent": len(records), "in_window": len(in_window),
        "finished_in_window": len(finished), "tokens_in_window": tokens_in_window,
        "compiles_in_window": out["compiles_in_window"], "engine_errors": errors.messages[:3],
        "first_failures": [r.error or f"{len(r.tokens)}/{r.request.max_tokens} tokens, HTTP {r.status}" for r in failed[:3]],
        "counters": {k: after[k] - before[k] for k in after if isinstance(after[k], (int, float)) and k not in ("resident", "waiting", "kv_used", "kv_total")},
        "resident_at_close": after["resident"], "waiting_at_close": after["waiting"], "kv_used_at_close": after.get("kv_used"),
        "backlog_after_drain": final["waiting"],
    }
    out["facts"] = {
        "kind": "serving", "window_s": seconds, "open_at": open_at, "close_at": close_at, "records": records,
        "in_window": in_window, "before": before, "after": after, "slice": slice_facts, "config": cfg,
        "decode_chunk": int(batcher.decode_chunk), "admit_chunk": chunk, "block_size": int(batcher.block_size or 1),
        "timeout_s": timeout_s, "chips": int(cell["chips"]),
    }

    # ---- free the program's state, then compare what the timed requests returned with the plain reference
    n_check = int(cell["check"]["requests"])
    pool = [
        {"prompt_tokens": len(r.request.prompt), "output_tokens": len(r.tokens), "session": r.request.session, "ask": r.request.ask}
        for r in finished
    ]
    picked = [finished[i] for i in compare.sample_requests(pool, n_check, args.seed)]
    del gen, batcher, app, server
    gc.unfreeze()
    gc.collect()
    limits = cell.get("limits", {})
    started = time.monotonic()
    gaps: List[float] = []
    lp_diffs: List[float] = []
    checked_tokens = 0
    for r in picked:
        prompt, served = r.request.prompt, r.tokens
        rows = [len(prompt) - 1 + i for i in range(len(served))]
        logits = reference.logits_at(weights, cfg, list(prompt) + list(served[:-1]), rows, pad_to=int(cell["check"].get("pad_to", 512)))
        gaps.extend(compare.token_gaps(logits, served).tolist())
        if r.logprobs and len(r.logprobs) == len(served):
            lp_diffs.extend(compare.logprob_diffs(logits, served, r.logprobs).tolist())
        checked_tokens += len(served)
    out["early"]["check"] = {
        "requests": len(picked), "tokens": checked_tokens, "seconds": time.monotonic() - started,
        "prompt_tokens": [len(r.request.prompt) for r in picked], "asks": [r.request.ask for r in picked],
        "logprob_diff_max": max(lp_diffs) if lp_diffs else None, "logprob_diff_mean": float(np.mean(lp_diffs)) if lp_diffs else None,
        "token_gap_mean": float(np.mean(gaps)) if gaps else None, "flipped_tokens": int(sum(1 for g in gaps if g > 0)),
    }
    # the widest gap catches a wrong token; the mean square of the log-probabilities' differences is the
    # rounding noise's power, which is what a lower precision raises (PERF.md, "How correct is decided")
    out["numbers"] = [
        ("token_gap_max", max(gaps) if gaps else float("inf"), limits.get("token_gap_max")),
        ("logprob_mse", float(np.mean(np.square(lp_diffs))) if lp_diffs else float("inf"), limits.get("logprob_mse")),
        ("requests_failed", float(out["failed"]), 0.0),
        ("requests_checked_short", float(max(0, min(n_check, len(in_window)) - len(picked))), 0.0),
        ("engine_errors", float(len(errors.messages)), 0.0),
    ]
    return out
