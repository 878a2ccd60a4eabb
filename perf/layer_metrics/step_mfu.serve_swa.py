"""model step (models/generate.py, models/smallthinker.py): model FLOPs that the window's prefilled and decoded
tokens require on this chip's share / (window x chips x bf16 peak), in percent: the share of the whole step. Token
counts come from the client's records; the pairs routed to held experts (stats()["moe"]["local_pairs"]) and the
positions the sliding layers' decode reads touched (stats()["window"]["window_positions_read"]) from the program's
counters (decisions, not work done); FLOPs from perf/work_swa.py."""

from perf import work_swa
from perf.layer_metrics import _common


def read(facts, trace, peak):
    before, after = facts.get("before", {}), facts.get("after", {})
    if peak is None or facts.get("kind") != "serving" or "window_positions_read" not in after:
        return None
    cfg, t0, t1 = facts["config"], facts["open_at"], facts["close_at"]
    delta = lambda name: after[name] - before[name]  # noqa: E731
    # every pair routed here, decode's and the chunks', in the decode term; the windowed reads' positions are decode's
    flops = work_swa.decode_flops(
        cfg, _common.tokens_between(facts["records"], t0, t1), delta("moe_local_pairs"), delta("window_positions_read")
    )
    flops += sum(work_swa.prefill_flops(cfg, len(r.request.prompt)) for r in _common.first_tokens_between(facts["records"], t0, t1))
    if flops <= 0:
        return None
    return 100.0 * flops / (facts["window_s"] * facts["chips"] * peak["bf16_flops_per_s"])
