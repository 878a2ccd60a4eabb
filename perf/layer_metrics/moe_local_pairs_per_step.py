"""engine (serving/continuous.py over models/moe.py ExpertShare): token-expert pairs that fell on held experts, per
decode step and expert layer, over the window (program counters stats()["moe"]["decode"]["local_pairs"] and
decode_dispatches): how full the engine keeps the held experts (all slots live: slots x top-k x held / routed)."""

from perf import work_moe


def read(facts, trace, peak):
    if facts.get("kind") != "serving" or "moe_decode_local_pairs" not in facts.get("after", {}):
        return None
    before, after = facts["before"], facts["after"]
    steps = (after["decode_dispatches"] - before["decode_dispatches"]) * facts["decode_chunk"]
    layers = work_moe.layer_counts(facts["config"])[1]
    if steps <= 0 or layers <= 0:
        return None
    return (after["moe_decode_local_pairs"] - before["moe_decode_local_pairs"]) / (steps * layers)
