"""kernels (XLA program decode_steps of a hybrid share: KDA layers' recurrent state a slot, an MLA layer's latent
pages): least time for a decode step's algorithmic work (the fixed weights and the head slice once + one expert's
bytes for every held expert a pair fell on + the recurrent state read and written once a live row a KDA layer +
each live position's latent once an MLA layer, against the memory peak; the recurrence's and the absorbed read's
FLOPs against the compute peak; the larger) / device time of the decode_steps program per step, in percent. Device
time from the trace by program name; experts hit, pairs routed here and rows updated from the program's counters
over the traced slice; work from perf/work_kda.py."""

from perf import work_kda
from perf.layer_metrics import _common


def read(facts, trace, peak):
    if facts.get("kind") != "serving" or not facts.get("slice") or peak is None:
        return None
    measured = _common.program(trace, ["decode_steps"])
    s = facts["slice"]
    if not measured or "state_rows_updated" not in s["after"] or "moe_decode_experts_hit" not in s["after"]:
        return None
    contexts = _common.tokens_between(facts["records"], s["t0"], s["t1"])
    delta = lambda name: s["after"][name] - s["before"][name]  # noqa: E731
    dispatches = delta("decode_dispatches")
    if not contexts or dispatches <= 0:
        return None
    steps = dispatches * facts["decode_chunk"]
    least, _bound = work_kda.decode_least_seconds(
        facts["config"], peak, steps, contexts, delta("moe_decode_experts_hit"), delta("moe_decode_local_pairs"),
        delta("state_rows_updated"),
    )
    return 100.0 * (least / steps) / (measured["seconds"] / (measured["calls"] * facts["decode_chunk"]))
