"""model step (models/generate.py, models/bailing_hybrid.py): model FLOPs that the window's prefilled and decoded
tokens require on this chip's share / (window x chips x bf16 peak), in percent: the share of the whole step. Token
counts come from the client's records, the pairs routed to held experts from the program's counter
(stats()["moe"]["local_pairs"]: routing decisions, not work done); FLOPs from perf/work_kda.py (a KDA layer as the
recurrence, 7 d^2 a head a token; an MLA layer's prefill expanded over the causal keys, its decode absorbed;
whatever the program does)."""

from perf import work_kda
from perf.layer_metrics import _common


def read(facts, trace, peak):
    if peak is None or facts.get("kind") != "serving" or "state_rows_updated" not in facts.get("after", {}):
        return None
    cfg, t0, t1 = facts["config"], facts["open_at"], facts["close_at"]
    flops = sum(work_kda.decode_token_flops(cfg, c) for c in _common.tokens_between(facts["records"], t0, t1))
    for r in _common.first_tokens_between(facts["records"], t0, t1):
        n = len(r.request.prompt)
        flops += work_kda.prefill_flops(cfg, n, work_kda.prompt_causal_pairs(cfg, n), 1.0)
    flops += work_kda.routed_flops(cfg, facts["after"]["moe_local_pairs"] - facts["before"]["moe_local_pairs"])
    if flops <= 0:
        return None
    return 100.0 * flops / (facts["window_s"] * facts["chips"] * peak["bf16_flops_per_s"])
