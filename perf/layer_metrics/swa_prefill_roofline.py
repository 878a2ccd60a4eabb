"""kernels (XLA program prefill_chunk of a window-and-global routed share): least time for a prefill chunk's
algorithmic work (the fixed weights once + the held experts hit + the keys and values the chunk's reads needed,
a windowed layer's from its window's first, and its own written, against the memory peak; the fixed matrices, the
query-key pairs and the pairs routed here, against the compute peak; the larger) / device time of the
prefill_chunk program per call, in percent. Device time from the trace by program name; chunks, tokens, the key
positions needed (kv_positions_needed, summed over the layers), experts hit and pairs routed here from the
program's counters between the two snapshots around the traced slice, per chunk (as mla_prefill_roofline.py: the
snapshots span more chunks than the trace holds); work from perf/work_swa.py."""

from perf import work_swa
from perf.layer_metrics import _common


def read(facts, trace, peak):
    if facts.get("kind") != "serving" or not facts.get("slice") or peak is None:
        return None
    measured = _common.program(trace, ["prefill_chunk"])
    s = facts["slice"]
    if not measured or "window_positions_read" not in s["after"] or "kv_positions_needed" not in s["after"]:
        return None
    delta = lambda name: s["after"][name] - s["before"][name]  # noqa: E731
    chunks, tokens = delta("prefill_chunks"), delta("prefill_chunk_tokens")
    if chunks <= 0 or tokens <= 0:
        return None
    hit = delta("moe_experts_hit") - delta("moe_decode_experts_hit")
    pairs = delta("moe_local_pairs") - delta("moe_decode_local_pairs")
    least, _bound = work_swa.prefill_least_seconds(facts["config"], peak, chunks, tokens, delta("kv_positions_needed"), hit, pairs)
    return 100.0 * (least / chunks) / (measured["seconds"] / measured["calls"])
