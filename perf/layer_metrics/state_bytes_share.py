"""engine (serving/continuous.py over models/layers.py KimiDeltaAttention): the recurrent state's read and write
(2 x a KDA layer's state a live row a step: program counter stats()["state"]["state_rows_updated"]) as a share of
the bytes the window's decode steps had to move (perf/work_kda.decode_bytes: fixed weights, experts hit, state,
latent), in percent: how much of a decode step is the slot state, the number that says whether this cell measures
what it was added for."""

from perf import work_kda
from perf.layer_metrics import _common


def read(facts, trace, peak):
    before, after = facts.get("before", {}), facts.get("after", {})
    if facts.get("kind") != "serving" or "state_rows_updated" not in after or "moe_decode_experts_hit" not in after:
        return None
    delta = lambda name: after[name] - before[name]  # noqa: E731
    steps = delta("decode_dispatches") * facts["decode_chunk"]
    contexts = _common.tokens_between(facts["records"], facts["open_at"], facts["close_at"])
    if steps <= 0 or not contexts:
        return None
    moved = work_kda.decode_bytes(facts["config"], steps, contexts, delta("moe_decode_experts_hit"), delta("state_rows_updated"))
    return 100.0 * moved["state"] / sum(moved.values())
