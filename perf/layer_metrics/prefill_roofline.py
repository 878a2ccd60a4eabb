"""kernels (XLA program prefill_chunk): least time for one admission chunk (the larger of its FLOPs over the
compute peak and its bytes — weights once, KV written so far read, the chunk's KV written — over the memory
peak), averaged over the chunks of the requests admitted in the traced slice / device time of the
prefill_chunk program per call, in percent."""

from perf import work
from perf.layer_metrics import _common


def read(facts, trace, peak):
    if peak is None:  # no chip: no share of a peak
        return None
    if facts.get("kind") != "serving" or not facts.get("slice") or not facts.get("admit_chunk"):
        return None
    measured = _common.program(trace, ["prefill_chunk"])
    if not measured:
        return None
    s, cfg, chunk = facts["slice"], facts["config"], facts["admit_chunk"]
    admitted = _common.first_tokens_between(facts["records"], s["t0"], s["t1"])
    least, chunks = 0.0, 0
    for r in admitted:
        prompt = len(r.request.prompt)
        cached = _common.cached_estimate(r.request, facts["block_size"])
        n = -(-(prompt - cached) // chunk)
        by_compute = work.prefill_flops(cfg, prompt, cached) / peak["bf16_flops_per_s"]
        by_memory = work.prefill_bytes(cfg, prompt, cached, chunk) / peak["hbm_bytes_per_s"]
        least += max(by_compute, by_memory)
        chunks += n
    if chunks <= 0:
        return None
    return 100.0 * (least / chunks) / (measured["seconds"] / measured["calls"])
