"""model step (models/generate.py): model FLOPs of the prompt tokens prefilled in the window (those the
prefix cache skipped left out) / (window x chips x bf16 peak), in percent: the whole prefill path's share of
the chip, beside prefill_roofline. Counts from the client's records and the prefix cache's counter; FLOPs
from perf/work.py."""

from perf.layer_metrics import _common


def read(facts, trace, peak):
    if peak is None:  # no chip: no share of a peak
        return None
    if facts.get("kind") != "serving":
        return None
    flops = _common.prefill_flops(facts)
    if flops <= 0:
        return None
    return 100.0 * flops / (facts["window_s"] * facts["chips"] * peak["bf16_flops_per_s"])
