"""kernels (XLA program decode_steps): least time for a decode step's algorithmic work (weights once +
live KV of the rows' real lengths against the memory peak, FLOPs against the compute peak; the larger) /
device time of the decode_steps program per step, in percent. Device time from the trace by program name."""

from perf import work
from perf.layer_metrics import _common


def read(facts, trace, peak):
    if facts.get("kind") != "serving" or not facts.get("slice"):
        return None
    measured = _common.program(trace, ["decode_steps"])
    if not measured:
        return None
    s = facts["slice"]
    contexts = _common.tokens_between(facts["records"], s["t0"], s["t1"])
    dispatches = s["after"]["decode_dispatches"] - s["before"]["decode_dispatches"]
    if not contexts or dispatches <= 0:
        return None
    steps = dispatches * facts["decode_chunk"]
    least, _bound = work.decode_least_seconds(facts["config"], peak, steps, contexts)
    return 100.0 * (least / steps) / (measured["seconds"] / (measured["calls"] * facts["decode_chunk"]))
