"""kernels (XLA program decode_steps of a window-and-global routed share): least time for a decode step's
algorithmic work (the fixed weights and the head once + one expert's bytes for every held expert a pair fell on +
2 x KV heads x head_dim x 2 bytes a position a layer, the global layers at the row's length and the windowed ones
at the positions their reads touched, and the row each token writes, against the memory peak; the same
positions' attention, the fixed matrices and the pairs routed here, against the compute peak; the larger) / device
time of the decode_steps program per step, in percent. Device time from the trace by program name; experts hit,
pairs routed here and the windowed positions read from the program's counters over the traced slice; work from
perf/work_swa.py."""

from perf import work_swa
from perf.layer_metrics import _common


def read(facts, trace, peak):
    if facts.get("kind") != "serving" or not facts.get("slice") or peak is None:
        return None
    measured = _common.program(trace, ["decode_steps"])
    s = facts["slice"]
    if not measured or "window_positions_read" not in s["after"] or "moe_decode_experts_hit" not in s["after"]:
        return None
    contexts = _common.tokens_between(facts["records"], s["t0"], s["t1"])
    delta = lambda name: s["after"][name] - s["before"][name]  # noqa: E731
    dispatches = delta("decode_dispatches")
    if not contexts or dispatches <= 0:
        return None
    steps = dispatches * facts["decode_chunk"]
    least, _bound = work_swa.decode_least_seconds(
        facts["config"], peak, steps, contexts, delta("moe_decode_experts_hit"), delta("moe_decode_local_pairs"),
        delta("window_positions_read"),
    )
    return 100.0 * (least / steps) / (measured["seconds"] / (measured["calls"] * facts["decode_chunk"]))
