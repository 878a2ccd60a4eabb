"""One chip's share of an afmoe decoder served through the program's normal path, under one traffic mix.

The same path as ``perf/systems/decoder_serving.py`` (``POST /v1/completions`` on
``model.serve()`` -> ``ServingApp`` -> ``ContinuousBatcher`` -> ``Generator`` ->
paged KV and the radix prefix cache), with ``AfmoeTransformer`` in ``Llama``'s
place and ``perf/reference/afmoe_decoder.py`` as the plain reference. The server,
the engine-error handler, the app and the fault are that file's own, imported.

**``run`` is a restatement of ``decoder_serving.run``** (that file's lines
187-343 as of PR 25, edited nowhere): a function cannot be handed another
engine, reference or compared number without an edit there, and binding its
code object to other globals breaks silently when it gains a helper. The lines
that differ are marked ``# differs:`` and are four: this file's
``build_engine``, ``_counters`` and reference (the int8 one under the control);
the server's default deadline, a deployment's setting read from the cell's file
(``serve.default_deadline_ms`` -> ``ServingApp.configure_overload``); and a
third compared number. Every other line is a copy, to be deleted when a
``benchmark`` PR gives ``decoder_serving.run`` those four parameters (PERF.md,
section 7).

**Why a third number.** A routed layer makes the comparison heavy-tailed: where
a token's k-th and (k+1)-th expert scores lie closer than the rounding noise of
the bfloat16 stream, program and float32 reference choose different experts, and
that token's log-probability moves by tenths where its neighbours' move by
hundredths (shown on the chip, token by token: PERF.md, section 2). The mean
square (``logprob_mse``) is then set by a few such tokens, in a sound run and
under the int8 control alike, and cannot tell them apart; the MEDIAN square
(``logprob_sq_median``) is the rounding noise of the bulk, which is what a lower
precision raises. ``token_gap_max`` and ``logprob_mse`` stand against a wrong
token, which moves them by orders of magnitude more than a changed choice of
expert does (readings in PERF.md, "How correct is decided").
"""

from __future__ import annotations

import asyncio
import functools
import gc
import threading
import time
import types
from typing import Any, Dict, List, Mapping, Optional

import numpy as np

from perf import compare
from perf.reference import afmoe_decoder as reference
from perf.systems import decoder_serving as base
from perf.traffic import client

# imported here, not where they are used: a program without the model (a commit before it) fails as this file is
# imported, within seconds, before any weight is made
from unionml_tpu.models import AfmoeConfig, AfmoeTransformer

Server, EngineErrors, build_app, plant_fault = base.Server, base.EngineErrors, base.build_app, base.plant_fault
_percentile = base._percentile


def module_config(cfg: Mapping[str, Any], **overrides: Any):
    """The configuration file's keys as the program's ``AfmoeConfig``."""
    import jax.numpy as jnp

    return AfmoeConfig(**{**dict(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        hidden_dim=cfg["intermediate_size"], moe_hidden_dim=cfg["moe_intermediate_size"],
        n_experts=cfg["router_experts"], experts_held=(cfg.get("experts_first", 0), cfg["num_experts"]),
        k=cfg["num_experts_per_tok"], n_shared_experts=cfg["num_shared_experts"], n_dense_layers=cfg["num_dense_layers"],
        layer_types=tuple(cfg["layer_types"]), sliding_window=cfg["sliding_window"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), score_func=cfg["score_func"], route_norm=bool(cfg["route_norm"]),
        route_scale=float(cfg["route_scale"]), mup_enabled=bool(cfg["mup_enabled"]),
        max_seq_len=cfg["max_position_embeddings"], param_dtype=jnp.bfloat16,
        dtype=jnp.dtype(cfg["precision"]["compute_dtype"]),
    ), **overrides})


def build_engine(cfg: Mapping[str, Any], cell: Mapping[str, Any], weights: Any, control: Optional[str]):
    """AfmoeTransformer + Generator + ContinuousBatcher at the configuration's sizes."""
    from unionml_tpu import models
    from unionml_tpu.models import GenerationConfig, Generator
    from unionml_tpu.serving import ContinuousBatcher

    engine = {**cfg["engine"], **cell["engine"]}
    chunk = int(engine["admit_chunk"])
    max_prompt = int(engine.pop("max_prompt_tokens"))
    max_new = int(engine.pop("max_new_tokens"))
    buckets = tuple(range(chunk, -(-max_prompt // chunk) * chunk + 1, chunk))
    mesh = rules = None
    if cfg.get("mesh"):
        from unionml_tpu import MeshSpec

        mesh = MeshSpec(**{"data": 1, **cfg["mesh"]}).build()
        rules = getattr(models, cfg["partition_rules"])()
    if control not in (None, "int8"):
        raise ValueError(f"unknown control precision {control!r}")
    # the engine is the sound one under the control too: the lower precision is put into the reference (``run``)
    gen_cfg = GenerationConfig(max_new_tokens=max_new, temperature=0.0, prompt_buckets=buckets)
    gen = Generator(AfmoeTransformer(module_config(cfg)), weights, gen_cfg, mesh=mesh, partition_rules=rules)
    return gen, ContinuousBatcher(gen, **engine)


def _counters(batcher: Any) -> Dict[str, Any]:
    """``decoder_serving``'s counters plus the routing's: all dispatches' and the decode dispatches' alone."""
    flat = base._counters(batcher)
    stats = batcher.stats()
    moe = stats.get("moe", {})
    flat.update({f"moe_{k}": v for k, v in moe.items() if k != "decode"})
    flat.update({f"moe_decode_{k}": v for k, v in moe.get("decode", {}).items()})
    if "decode_window_pages_skipped" in stats:
        flat["decode_window_pages_skipped"] = stats["decode_window_pages_skipped"]
    return flat


#: the reference as ``run`` calls it, its matrices rounded to int8: the cell's lower-precision control. The program's
#: own ``quantize="int8"`` path does not fit this cell: with int8 pages the decode read is the gather, whose logical
#: copy of the rows' whole tables is 9.96 GB here, and with int8 weights alone the dequantized experts beside the
#: pool leave the allocator 269 MB short (my chip runs, PR 26). So, as the training cell does, the control computes
#: the reference with int8 operands in the program's place
_int8_reference = types.SimpleNamespace(
    make_weights=reference.make_weights, logits_at=functools.partial(reference.logits_at, int8_weights=True)
)


def run(ctx: Any) -> Dict[str, Any]:
    """One run of an afmoe serving cell: ``decoder_serving.run`` restated (module docstring), the lines that
    differ marked."""
    from unionml_tpu._logging import logger

    cfg, cell, mix, args = ctx.config, ctx.cell, ctx.mix, ctx.args
    errors = EngineErrors()
    logger.addHandler(errors)
    seconds = float(args.seconds)
    ramp_s = float(mix.get("ramp_s", 0.0))
    timeout_s = float(mix.get("request_timeout_s", 120.0))
    want_logprobs = bool(mix.get("logprobs", True))

    plain = _int8_reference if args.control == "int8" else reference  # differs: the control is the reference's
    weights = plain.make_weights(cfg, args.seed)
    gen, batcher = build_engine(cfg, cell, weights, args.control)  # differs: this file's engine (and _counters)
    batcher.warmup()
    plant_fault(gen, args.fault, cfg["vocab_size"])
    chunk = int(batcher.admit_chunk or 0)
    schedule = ctx.traffic.requests(mix, args.seed, cfg["vocab_size"], ramp_s + seconds)
    app = build_app(batcher, weights)
    # differs: a deployment that works off a backlog sets the server's default deadline (30 s, an interactive
    # front's: unionml_tpu/defaults.py) to what its callers wait for; the key is the cell's, absent -> the default
    app.configure_overload(default_deadline_ms=cell.get("serve", {}).get("default_deadline_ms"))
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
        logits = plain.logits_at(weights, cfg, list(prompt) + list(served[:-1]), rows, pad_to=int(cell["check"].get("pad_to", 512)))
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
        # differs: the rounding noise of the bulk, which a few flipped choices of expert do not set (module docstring)
        ("logprob_sq_median", float(np.median(np.square(lp_diffs))) if lp_diffs else float("inf"), limits.get("logprob_sq_median")),
    ]
    return out
