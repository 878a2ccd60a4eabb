"""Replica-serving benchmark: aggregate decode throughput vs ``--dp-replicas``.

Metric: aggregate tokens/sec across a fixed fleet of concurrent streams served
by a :class:`~unionml_tpu.serving.ReplicaSet`, as the replica count grows with
PER-REPLICA capacity held fixed (slots, decode chunk) — the fleet-operator
question ("I add a chip, what do I get?"), not the single-engine batching
question ``bench_continuous.py`` already answers.

The engine is a DISPATCH-BOUND SYNTHETIC: per-replica tiny-Llama engines whose
jitted decode is wrapped with a fixed dispatch latency (the regime where
host dispatch overhead dominates the chunk, so a single
engine's wall clock is its dispatch count regardless of resident rows). Under
that regime a lone engine serializes the stream waves that exceed its slots;
replicas run their dispatch pipelines in parallel, so aggregate throughput
should scale ~linearly until replicas outnumber stream waves. ``vs_baseline``
is the scaling factor of the largest replica count over 1 replica, and
``speedup_dp2`` pins the 2-vs-1 point (the acceptance gate: >= 1.5x).

CPU-substrate by design: it measures the replica
layer's scheduling + dispatch overlap on the emulated 8-device host mesh, not
chip throughput. There is no reference analog — the reference serves one
request at a time through one process.

Every printed line goes to stderr except the final JSON metric line (stdout).
Usage: ``python benchmarks/bench_replica_serving.py [--dp-replicas=1,2,4]``.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# pin the emulated CPU mesh BEFORE jax imports: each replica should own a
# distinct (emulated) device, and this lane must never take the chip
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np

from benchmarks.common import Timer, emit, log

_SMALL = os.environ.get("BENCH_SMALL") == "1"
PROMPT_LEN = 8 if _SMALL else 16
NEW_TOKENS = 8 if _SMALL else 32
DECODE_CHUNK = 4
SLOTS = 2  # per replica — fixed, so replicas are the only capacity knob
STREAMS = 8 if _SMALL else 16
#: synthetic per-dispatch latency (seconds): large against the tiny model's
#: compute per chunk, so dispatch count — not row count — sets the wall clock
DISPATCH_S = 0.02
REPLICAS = (1, 2) if _SMALL else (1, 2, 4)


def _parse_replicas(argv) -> tuple:
    for i, arg in enumerate(argv):
        if arg.startswith("--dp-replicas"):
            raw = arg.split("=", 1)[1] if "=" in arg else argv[i + 1]
            counts = tuple(sorted({int(n) for n in raw.split(",")}))
            if not counts or min(counts) < 1:
                raise SystemExit(f"--dp-replicas needs positive counts, got {raw!r}")
            return counts
    return REPLICAS


def run_streams(replica_set, prompts) -> int:
    """Drive len(prompts) concurrent streams to completion; returns tokens."""
    totals = [0] * len(prompts)

    def worker(i: int) -> None:
        for chunk in replica_set.submit(prompts[i]):
            totals[i] += int(np.asarray(chunk).size)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sum(totals)


def main() -> None:
    counts = _parse_replicas(sys.argv[1:])
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")

    from unionml_tpu.models import GenerationConfig, Llama, LlamaConfig
    from unionml_tpu.serving import ReplicaSet

    log(f"devices: {len(jax.devices())} ({jax.devices()[0].platform}), replica counts: {counts}")
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

    rates = {}
    for n in counts:
        replica_set = ReplicaSet.build(
            module, params, cfg, replicas=n, slots=SLOTS, decode_chunk=DECODE_CHUNK
        )
        try:
            replica_set.warmup()  # compiles first, so the sleep wrap below never pays it
            for batcher in replica_set.batchers:
                # the synthetic dispatch-bound regime: every device round-trip
                # (admission prefill AND shared decode chunk) costs a fixed
                # latency that dwarfs the tiny model's compute — sleeps release
                # the GIL, so overlap across replicas is real parallelism
                real_decode, real_prefill = batcher.gen._decode, batcher._prefill_row

                def slow_decode(*args, _real=real_decode, **kwargs):
                    time.sleep(DISPATCH_S)
                    return _real(*args, **kwargs)

                def slow_prefill(*args, _real=real_prefill, **kwargs):
                    time.sleep(DISPATCH_S)
                    return _real(*args, **kwargs)

                batcher.gen._decode = slow_decode
                batcher._prefill_row = slow_prefill
            with Timer() as t:
                tokens = run_streams(replica_set, prompts)
            rates[n] = tokens / t.elapsed
            stats = replica_set.stats()
            log(
                f"replicas {n}: {tokens} tokens in {t.elapsed:.2f}s -> {rates[n]:.0f} tok/s "
                f"aggregate ({stats['decode_dispatches']} dispatches, "
                f"routing {stats['scheduler']['submitted']})"
            )
        finally:
            replica_set.close()

    top = max(counts)
    base = rates[min(counts)]
    extras = {f"tok_s_dp{n}": rates[n] for n in counts}
    if 2 in rates and 1 in rates:
        extras["speedup_dp2"] = rates[2] / rates[1]
    emit(
        "replica_serving_throughput",
        rates[top],
        "tok/s",
        rates[top] / base,
        replicas=top,
        streams=STREAMS,
        slots_per_replica=SLOTS,
        dispatch_ms=DISPATCH_S * 1e3,
        platform="cpu",
        **extras,
    )


if __name__ == "__main__":
    main()
