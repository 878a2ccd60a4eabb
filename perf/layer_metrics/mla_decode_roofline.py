"""kernels (XLA program decode_steps of a latent-attention share): least time for a decode step's algorithmic work
(the fixed weights and the head slice once + one expert's bytes for every held expert a pair fell on + each live
position's latent once a layer, 1,152 B, against the memory peak; the absorbed form's FLOPs against the compute
peak; the larger) / device time of the decode_steps program per step, in percent. Device time from the trace by
program name; experts hit and pairs routed here from the program's decode counters over the traced slice; work
from perf/work_mla.py."""

from perf import work_mla
from perf.layer_metrics import _common


def read(facts, trace, peak):
    if facts.get("kind") != "serving" or not facts.get("slice") or peak is None:
        return None
    measured = _common.program(trace, ["decode_steps"])
    s = facts["slice"]
    if not measured or "latent_positions_read" not in s["after"] or "moe_decode_experts_hit" not in s["after"]:
        return None
    contexts = _common.tokens_between(facts["records"], s["t0"], s["t1"])
    dispatches = s["after"]["decode_dispatches"] - s["before"]["decode_dispatches"]
    if not contexts or dispatches <= 0:
        return None
    steps = dispatches * facts["decode_chunk"]
    hit = s["after"]["moe_decode_experts_hit"] - s["before"]["moe_decode_experts_hit"]
    pairs = s["after"]["moe_decode_local_pairs"] - s["before"]["moe_decode_local_pairs"]
    least, _bound = work_mla.decode_least_seconds(facts["config"], peak, steps, contexts, hit, pairs)
    return 100.0 * (least / steps) / (measured["seconds"] / (measured["calls"] * facts["decode_chunk"]))
