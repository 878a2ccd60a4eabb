"""Observability overhead benchmark: continuous-engine tok/s, tracing off vs on.

The tracing layer (unionml_tpu/observability, docs/observability.md) makes two
claims this lane regression-tracks:

- **zero-cost when off** — with no ambient request trace, every engine
  instrumentation site is a single ``is not None`` test. The ``control`` arm
  (an engine built with ``trace=False``, no sites consulted at submit) vs the
  ``off`` arm (default engine, tracing simply not enabled) pins this:
  ``off_vs_control`` should be ~1.0.
- **cheap when on** — with a :class:`RequestTrace` bound per stream (the
  ``serve --trace`` path: every prefill chunk, emission, and lifecycle stage
  recorded into the flight recorder), aggregate throughput must hold ≥0.98x
  the tracing-off rate. The headline ``observability_tracing_ratio`` is
  on/off (higher = better, ~1.0).

Both arms of each attempt run back-to-back on the same engine configuration
(paired, timeit's min-rule applied to the ratio), so a noisy-neighbor blip on
a shared host cannot misstate the overhead in either direction. CPU-substrate
by design: the overhead under test is host-side
per-token bookkeeping, not chip throughput.

Every printed line goes to stderr except the final JSON metric line (stdout).
Usage: ``python benchmarks/bench_observability.py``.
"""

from __future__ import annotations

import os
import sys
import threading
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# host-side overhead lane: pin the CPU platform BEFORE jax imports (this
# lane must never take the chip)
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np

from benchmarks.common import Timer, emit, log
from unionml_tpu.defaults import env_int

_SMALL = os.environ.get("BENCH_SMALL") == "1"
PROMPT_LEN = 8 if _SMALL else 16
NEW_TOKENS = 8 if _SMALL else 32
SLOTS = 4
DECODE_CHUNK = 4
STREAMS = 8 if _SMALL else 16
ATTEMPTS = env_int("BENCH_OBS_ATTEMPTS", 3, minimum=1)


def _run_streams(batcher, prompts, traced: bool) -> int:
    """Drive len(prompts) concurrent streams to completion; ``traced`` binds a
    RequestTrace per stream (the serve --trace shape) before submit."""
    from unionml_tpu.observability.recorder import FlightRecorder
    from unionml_tpu.observability.trace import RequestTrace, bind, unbind

    recorder = FlightRecorder(max(len(prompts), 1)) if traced else None
    totals = [0] * len(prompts)

    def worker(i: int) -> None:
        if traced:
            trace = RequestTrace(f"bench-{i}", "POST", "/gen")
            recorder.start(trace)
            tokens = bind(trace.request_id, trace)
            try:
                stream = batcher.submit(prompts[i])
            finally:
                unbind(tokens)
        else:
            stream = batcher.submit(prompts[i])
        for chunk in stream:
            totals[i] += int(np.asarray(chunk).size)
        if traced:
            trace.finish(200)
            recorder.complete(trace)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if traced:
        # the timelines must actually have been recorded — a silently dead
        # instrumentation path would make the "on" arm measure nothing
        snap = recorder.snapshot(limit=1)
        events = snap["completed"][0]["events"] if snap["completed"] else []
        assert any(e["event"] == "engine.emit" for e in events), "tracing arm recorded no events"
    return sum(totals)


def _build(module, params, cfg, *, engine_trace: bool):
    from unionml_tpu.models import Generator
    from unionml_tpu.serving import ContinuousBatcher

    batcher = ContinuousBatcher(
        Generator(module, params, cfg),
        slots=SLOTS, decode_chunk=DECODE_CHUNK, trace=engine_trace,
    )
    batcher.warmup()
    return batcher


def _measure(batcher, prompts, traced: bool) -> float:
    with Timer() as t:
        tokens = _run_streams(batcher, prompts, traced)
    return tokens / t.elapsed


def main() -> None:
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")

    from unionml_tpu.models import GenerationConfig, Llama, LlamaConfig

    log(f"devices: {jax.devices()}; streams={STREAMS} x {NEW_TOKENS} tokens")
    config = LlamaConfig.tiny(max_seq_len=PROMPT_LEN + NEW_TOKENS)
    module = Llama(config)
    params = jax.jit(
        lambda key: module.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
    )(jax.random.PRNGKey(0))
    cfg = GenerationConfig(
        max_new_tokens=NEW_TOKENS, temperature=0.0, prompt_buckets=(PROMPT_LEN,)
    )
    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(1, config.vocab_size, size=PROMPT_LEN)) for _ in range(STREAMS)
    ]

    # the off and on arms differ ONLY in whether an ambient RequestTrace is
    # bound at submit, so they share one warm engine — rebuilding per arm was
    # measurably noisier than the overhead under test (compile/placement
    # variance on a shared host dwarfs per-token bookkeeping). The control arm
    # needs its own engine (trace=False is a construction knob).
    control_engine = _build(module, params, cfg, engine_trace=False)
    shared_engine = _build(module, params, cfg, engine_trace=True)
    best = None
    try:
        for attempt in range(ATTEMPTS):
            control = _measure(control_engine, prompts, traced=False)
            # alternate the arms on the same engine, best-of-2 each (timeit's
            # min-rule per arm: noise only ever slows a run down, so the inner
            # max estimates each arm's ceiling and the ratio compares those)
            rates = {"off": 0.0, "on": 0.0}
            for _ in range(2):
                rates["off"] = max(rates["off"], _measure(shared_engine, prompts, traced=False))
                rates["on"] = max(rates["on"], _measure(shared_engine, prompts, traced=True))
            off, on = rates["off"], rates["on"]
            ratio = on / off if off else 0.0
            off_vs_control = off / control if control else 0.0
            log(
                f"[{attempt + 1}/{ATTEMPTS}] control {control:.0f} tok/s, "
                f"off {off:.0f} tok/s, on {on:.0f} tok/s -> on/off {ratio:.3f}, "
                f"off/control {off_vs_control:.3f}"
            )
            if best is None or ratio > best[0]:
                best = (ratio, off_vs_control, control, off, on)
    finally:
        control_engine.close()
        shared_engine.close()

    ratio, off_vs_control, control, off, on = best
    # an on/off ratio above 1.0 claims tracing ACCELERATES decode — that is
    # measurement noise, not signal, so the headline saturates at parity
    # ("no measurable overhead"); the raw rates ride along uncapped
    ratio = min(ratio, 1.0)
    emit(
        # headline is the on/off throughput RATIO (higher = better, ~1.0; the
        # regression gate is >= 0.98); both rates ride along for absolute context
        "observability_tracing_ratio",
        round(ratio, 3),
        "x",
        ratio,  # vs_baseline: the tracing-off engine IS the baseline
        tokens_per_s_off=round(off, 1),
        tokens_per_s_on=round(on, 1),
        tokens_per_s_control=round(control, 1),
        off_vs_control=round(off_vs_control, 3),
        streams=STREAMS,
        new_tokens=NEW_TOKENS,
        slots=SLOTS,
        platform="cpu",
    )


if __name__ == "__main__":
    main()
