"""model step (models/generate.py, models/afmoe.py): model FLOPs that the window's prefilled and decoded tokens
require on this chip's share / (window x chips x bf16 peak), in percent. Token counts come from the client's
records, the pairs routed to held experts from the program's counter (stats()["moe"]["local_pairs"]: routing
decisions, not work done); FLOPs from perf/work_moe.py."""

from perf import work_moe
from perf.layer_metrics import _common


def read(facts, trace, peak):
    if peak is None or facts.get("kind") != "serving" or "moe_local_pairs" not in facts.get("after", {}):
        return None
    cfg, t0, t1 = facts["config"], facts["open_at"], facts["close_at"]
    flops = sum(work_moe.token_fixed_flops(cfg, c, head=True) for c in _common.tokens_between(facts["records"], t0, t1))
    flops += sum(work_moe.prefill_fixed_flops(cfg, len(r.request.prompt)) for r in _common.first_tokens_between(facts["records"], t0, t1))
    flops += work_moe.routed_flops(cfg, facts["after"]["moe_local_pairs"] - facts["before"]["moe_local_pairs"])
    if flops <= 0:
        return None
    return 100.0 * flops / (facts["window_s"] * facts["chips"] * peak["bf16_flops_per_s"])
