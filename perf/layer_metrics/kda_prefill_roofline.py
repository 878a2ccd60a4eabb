"""kernels (XLA program prefill_chunk of a hybrid share: the delta rule's chunk form in the KDA layers, the latent
read in the MLA layer): least time for a prefill chunk's algorithmic work (the fixed weights and the row's
recurrent state in and out once a chunk + the held experts hit + the latent rows the chunk causally needed, against
the memory peak; the fixed matrices, the recurrence a live position a KDA layer and the expanded attention over
the causal keys, against the compute peak; the larger) / device time of the prefill_chunk program per call, in
percent. Device time from the trace by program name; chunks, tokens, positions needed, experts hit and pairs routed
here from the program's counters between the two snapshots around the traced slice, per chunk (as
mla_prefill_roofline.py: the snapshots span more chunks than the trace holds); work from perf/work_kda.py."""

from perf import work_kda
from perf.layer_metrics import _common


def read(facts, trace, peak):
    if facts.get("kind") != "serving" or not facts.get("slice") or peak is None:
        return None
    measured = _common.program(trace, ["prefill_chunk"])
    s = facts["slice"]
    if not measured or "state_positions_needed" not in s["after"] or "moe_decode_experts_hit" not in s["after"]:
        return None
    delta = lambda name: s["after"][name] - s["before"][name]  # noqa: E731
    chunks, tokens = delta("prefill_chunks"), delta("prefill_chunk_tokens")
    if chunks <= 0 or tokens <= 0:
        return None
    hit = delta("moe_experts_hit") - delta("moe_decode_experts_hit")
    pairs = delta("moe_local_pairs") - delta("moe_decode_local_pairs")
    least, _bound = work_kda.prefill_least_seconds(
        facts["config"], peak, chunks, tokens, delta("latent_positions_needed"), hit, pairs, delta("state_positions_needed")
    )
    return 100.0 * (least / chunks) / (measured["seconds"] / measured["calls"])
