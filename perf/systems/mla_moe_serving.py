"""One chip's share of a latent-attention (MLA) routed-experts decoder served through the program's normal path.

The same path as ``perf/systems/decoder_serving.py`` (``POST /v1/completions`` on
``model.serve()`` -> ``ServingApp`` -> ``ContinuousBatcher`` -> ``Generator`` ->
the paged pool and the radix prefix cache), with ``Glm4MoeLiteTransformer`` in
``Llama``'s place — its pool holds one latent plane a layer, no keys and no
values — and ``perf/reference/glm4_moe_lite_decoder.py`` as the plain
reference. The server, the engine-error handler, the app and the fault are that
file's own, imported.

**``run`` is ``decoder_serving.run`` with its four fixed things as parameters**
(PERF.md section 7, item 10 (a)): ``engine`` (builds the Generator and the
engine), ``counters`` (the engine's cumulative counters, flat), ``references``
(the plain reference by ``--control``) and ``extra_numbers`` (compared numbers
beyond that file's five), and it reads the server's settings from the cell's
``serve`` section. Every other line is that function's, so a ``benchmark`` PR
that moves this ``run`` into ``decoder_serving.py`` deletes that file's window
and ``afmoe_serving.py``'s marked copy; this PR may edit neither.

The compared numbers are the afmoe cell's three, for its reason (a routed layer
makes the comparison heavy-tailed: ``logprob_sq_median`` is the bulk's rounding
noise, ``token_gap_max`` and ``logprob_mse`` stand against a wrong token).
"""

from __future__ import annotations

import asyncio
import functools
import gc
import threading
import time
import types
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from perf import compare
from perf.reference import glm4_moe_lite_decoder as reference
from perf.systems import decoder_serving as base
from perf.traffic import client

# imported here, not where they are used: a program without the model (a commit before it) fails as this file is
# imported, within seconds, before any weight is made
from unionml_tpu.models import Glm4MoeLiteConfig, Glm4MoeLiteTransformer

Server, EngineErrors, build_app, plant_fault = base.Server, base.EngineErrors, base.build_app, base.plant_fault
_percentile = base._percentile


def module_config(cfg: Mapping[str, Any], **overrides: Any):
    """The configuration file's keys as the program's ``Glm4MoeLiteConfig``."""
    import jax.numpy as jnp

    return Glm4MoeLiteConfig(**{**dict(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"], qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        hidden_dim=cfg["intermediate_size"], moe_hidden_dim=cfg["moe_intermediate_size"],
        n_experts=cfg["router_experts"], experts_held=(cfg.get("experts_first", 0), cfg["n_routed_experts"]),
        k=cfg["num_experts_per_tok"], n_shared_experts=cfg["n_shared_experts"], n_dense_layers=cfg["first_k_dense_replace"],
        route_norm=bool(cfg["norm_topk_prob"]), route_scale=float(cfg["routed_scaling_factor"]),
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]), max_seq_len=cfg["max_position_embeddings"],
        param_dtype=jnp.bfloat16, dtype=jnp.dtype(cfg["precision"]["compute_dtype"]),
    ), **overrides})


def build_engine(cfg: Mapping[str, Any], cell: Mapping[str, Any], weights: Any, control: Optional[str]):
    """Glm4MoeLiteTransformer + Generator + ContinuousBatcher at the configuration's sizes."""
    from unionml_tpu.models import GenerationConfig, Generator
    from unionml_tpu.serving import ContinuousBatcher

    engine = {**cfg["engine"], **cell["engine"]}
    chunk = int(engine["admit_chunk"])
    max_prompt = int(engine.pop("max_prompt_tokens"))
    max_new = int(engine.pop("max_new_tokens"))
    buckets = tuple(range(chunk, -(-max_prompt // chunk) * chunk + 1, chunk))
    if control not in (None, "int8"):
        raise ValueError(f"unknown control precision {control!r}")
    # the engine is the sound one under the control too: the lower precision is put into the reference (``references``)
    gen_cfg = GenerationConfig(max_new_tokens=max_new, temperature=0.0, prompt_buckets=buckets)
    gen = Generator(Glm4MoeLiteTransformer(module_config(cfg)), weights, gen_cfg)
    return gen, ContinuousBatcher(gen, **engine)


def _counters(batcher: Any) -> Dict[str, Any]:
    """``decoder_serving``'s counters plus the model's, under the flat names the afmoe cell gives the routing's
    (``moe_*``: all dispatches, ``moe_decode_*``: the decode dispatches alone) and ``latent_*`` for the latent reads."""
    flat = base._counters(batcher)
    stats = batcher.stats()
    moe = stats.get("moe", {})
    flat.update({f"moe_{k}": v for k, v in moe.items() if k != "decode"})
    flat.update({f"moe_decode_{k}": v for k, v in moe.get("decode", {}).items()})
    flat.update({k: v for k, v in stats.get("latent", {}).items() if k != "decode"})
    return flat


#: the plain reference by ``--control``: under ``int8`` its matrices are rounded to int8 and the program stays sound
#: (the program's own int8 path has no latent form: int8 pages over a latent layout raise)
REFERENCES = {
    None: reference,
    "int8": types.SimpleNamespace(
        make_weights=reference.make_weights, logits_at=functools.partial(reference.logits_at, int8_weights=True)
    ),
}


def median_square(lp_diffs: Sequence[float], limits: Mapping[str, Any]) -> List[Tuple[str, float, Any]]:
    """The rounding noise of the bulk, which a few flipped choices of expert do not set (module docstring)."""
    value = float(np.median(np.square(lp_diffs))) if len(lp_diffs) else float("inf")
    return [("logprob_sq_median", value, limits.get("logprob_sq_median"))]


def run(
    ctx: Any,
    engine: Callable[..., Any] = build_engine,
    counters: Callable[[Any], Dict[str, Any]] = _counters,
    references: Mapping[Optional[str], Any] = REFERENCES,
    extra_numbers: Callable[[Sequence[float], Mapping[str, Any]], List[Tuple[str, float, Any]]] = median_square,
) -> Dict[str, Any]:
    from unionml_tpu._logging import logger

    cfg, cell, mix, args = ctx.config, ctx.cell, ctx.mix, ctx.args
    errors = EngineErrors()
    logger.addHandler(errors)
    seconds = float(args.seconds)
    ramp_s = float(mix.get("ramp_s", 0.0))
    timeout_s = float(mix.get("request_timeout_s", 120.0))
    want_logprobs = bool(mix.get("logprobs", True))

    plain = references[args.control]
    weights = plain.make_weights(cfg, args.seed)
    gen, batcher = engine(cfg, cell, weights, args.control)
    batcher.warmup()
    plant_fault(gen, args.fault, cfg["vocab_size"])
    chunk = int(batcher.admit_chunk or 0)
    schedule = ctx.traffic.requests(mix, args.seed, cfg["vocab_size"], ramp_s + seconds)
    app = build_app(batcher, weights)
    # a deployment's own server settings (the cell's ``serve`` section; absent -> the program's defaults): a backlog's
    # deadline is what its callers wait for, not an interactive front's 30 s (unionml_tpu/defaults.py)
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
        before = counters(batcher)
        slice_facts = None
        if args.trace:
            offset = float(cell.get("trace_offset_s", min(2.0, seconds / 4)))
            length = min(float(cell.get("trace_seconds", 4.0)), max(seconds - offset - 0.5, 0.5))
            time.sleep(max(0.0, open_at + offset - time.monotonic()))
            s0, t0 = counters(batcher), time.monotonic()
            ctx.start_trace()
            time.sleep(length)
            ctx.stop_trace()
            t1, s1 = time.monotonic(), counters(batcher)
            slice_facts = {"t0": t0, "t1": t1, "before": s0, "after": s1}
        time.sleep(max(0.0, close_at - time.monotonic()))
        after = counters(batcher)
        out["compiles_in_window"] = ctx.compile_meter.count - compiles_before
        # ---- the window is closed; requests in flight finish (latencies count the wait)
        loader.join(timeout=float(mix.get("drain_s", 90.0)) + timeout_s)
        if loader.is_alive():
            raise RuntimeError("the load generator did not finish after the window closed")
        if load_error:
            raise load_error[0]
        final = counters(batcher)
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
        "backlog_after_drain": final["waiting"], "decode_attention_path": gen.decode_attention_path,
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
        *extra_numbers(lp_diffs, limits),
    ]
    return out
