"""Fleet-health overhead benchmark: continuous-engine tok/s, health engine on
vs off.

The fleet health & SLO layer (observability/{timeseries,slo,health}.py,
docs/observability.md "SLOs and fleet health") adds per-iteration bookkeeping
to the decode hot loop — windowed BucketRing feeds at every emission /
admission / shed, per-emission SLO target comparisons, and timestamped
TTFT/TBT reservoirs — plus a health/SLO evaluation whenever anything consults
``health()``. The claim this lane regression-tracks: with SLO targets ARMED
and a poller hammering ``health()``/``stats()``/``rates()`` at scrape-like
cadence (the worst realistic consumer pattern — the replica scheduler reads a
cached evaluation), aggregate throughput holds >= 0.98x an engine built with
``slo=False`` (the pre-health-engine engine, byte for byte).

Both arms of each attempt run back-to-back on equal engines warmed from the
same weights (paired, timeit's min-rule per arm), so a noisy-neighbor blip on
a shared host cannot misstate the overhead in either direction. CPU-substrate
by design: the overhead under test is host-side
bookkeeping, not chip throughput.

Every printed line goes to stderr except the final JSON metric line (stdout).
Usage: ``python benchmarks/bench_fleet_health.py``.
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
ATTEMPTS = env_int("BENCH_FLEET_HEALTH_ATTEMPTS", 3, minimum=1)
#: poller cadence (s): ~20 Hz is far denser than any real scraper; the cached
#: health TTL (0.5 s) means full evaluations still run at most ~2/s, exactly
#: the production shape
POLL_INTERVAL_S = 0.05


def _run_streams(batcher, prompts) -> int:
    totals = [0] * len(prompts)

    def worker(i: int) -> None:
        for chunk in batcher.submit(prompts[i]):
            totals[i] += int(np.asarray(chunk).size)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sum(totals)


def _measure(batcher, prompts, polled: bool) -> float:
    """tok/s over one full fan-out; ``polled`` runs the health consumer
    (health + stats + rates at scrape cadence) concurrently — the on-arm."""
    stop = threading.Event()

    def poll() -> None:
        while not stop.is_set():
            batcher.health()
            batcher.stats()
            batcher.rates()
            stop.wait(POLL_INTERVAL_S)

    poller = threading.Thread(target=poll) if polled else None
    if poller is not None:
        poller.start()
    try:
        with Timer() as t:
            tokens = _run_streams(batcher, prompts)
    finally:
        stop.set()
        if poller is not None:
            poller.join()
    return tokens / t.elapsed


def _build(module, params, cfg, *, slo):
    from unionml_tpu.models import Generator
    from unionml_tpu.serving import ContinuousBatcher

    batcher = ContinuousBatcher(
        Generator(module, params, cfg),
        slots=SLOTS, decode_chunk=DECODE_CHUNK, slo=slo,
    )
    batcher.warmup()
    return batcher


def main() -> None:
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")

    from unionml_tpu.models import GenerationConfig, Llama, LlamaConfig
    from unionml_tpu.observability.slo import SLOConfig

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

    # generous targets: the lane measures bookkeeping cost, and armed targets
    # that BREACH would measure the same code paths plus exemplar stamps —
    # pick the steady healthy state production sits in
    targets = SLOConfig(ttft_p95_ms=60_000.0, tbt_p99_ms=10_000.0, shed_ratio=0.05)
    engine_off = _build(module, params, cfg, slo=False)
    engine_on = _build(module, params, cfg, slo=targets)
    best = None
    try:
        for attempt in range(ATTEMPTS):
            # alternate the arms, best-of-2 each (timeit's min-rule per arm:
            # noise only ever slows a run down, so the inner max estimates
            # each arm's ceiling and the ratio compares those)
            rates = {"off": 0.0, "on": 0.0}
            for _ in range(2):
                rates["off"] = max(rates["off"], _measure(engine_off, prompts, polled=False))
                rates["on"] = max(rates["on"], _measure(engine_on, prompts, polled=True))
            off, on = rates["off"], rates["on"]
            ratio = on / off if off else 0.0
            log(
                f"[{attempt + 1}/{ATTEMPTS}] off {off:.0f} tok/s, on {on:.0f} tok/s "
                f"-> on/off {ratio:.3f}"
            )
            if best is None or ratio > best[0]:
                best = (ratio, off, on)
        # the armed engine's telemetry must actually have run — a silently
        # dead feed would make the "on" arm measure nothing
        stats = engine_on.stats()
        assert stats["rates"]["tokens_per_s"] > 0, "health engine recorded no token rate"
        assert stats["slo"]["state"] == "ok", f"bench traffic breached: {stats['slo']}"
    finally:
        engine_off.close()
        engine_on.close()

    ratio, off, on = best
    # a ratio above 1.0 claims the health engine ACCELERATES decode — that is
    # measurement noise, not signal, so the headline saturates at parity
    ratio = min(ratio, 1.0)
    emit(
        # headline is the on/off throughput RATIO (higher = better, ~1.0; the
        # regression gate is >= 0.98); both rates ride along for absolute context
        "fleet_health_overhead_ratio",
        round(ratio, 3),
        "x",
        ratio,  # vs_baseline: the slo=False engine IS the baseline
        tokens_per_s_off=round(off, 1),
        tokens_per_s_on=round(on, 1),
        streams=STREAMS,
        new_tokens=NEW_TOKENS,
        slots=SLOTS,
        poll_interval_s=POLL_INTERVAL_S,
        platform="cpu",
    )


if __name__ == "__main__":
    main()
