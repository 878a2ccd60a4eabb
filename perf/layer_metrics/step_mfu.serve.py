"""model step (models/generate.py): model FLOPs that the window's prefilled (not avoided) and decoded
tokens require / (window x chips x bf16 peak), in percent. Counts come from the client's records and the
prefix cache's counter; FLOPs from perf/work.py."""

from perf import work
from perf.layer_metrics import _common


def read(facts, trace, peak):
    if peak is None:  # no chip: no share of a peak
        return None
    if facts.get("kind") != "serving":
        return None
    cfg, t0, t1 = facts["config"], facts["open_at"], facts["close_at"]
    flops = sum(work.decoder_token_flops(cfg, c, head=True) for c in _common.tokens_between(facts["records"], t0, t1))
    flops += _common.prefill_flops(facts)
    if flops <= 0:
        return None
    return 100.0 * flops / (facts["window_s"] * facts["chips"] * peak["bf16_flops_per_s"])
