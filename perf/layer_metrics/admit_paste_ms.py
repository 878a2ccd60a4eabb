"""engine (XLA program _paged_admit_impl, the admission's paste: a prefilled row into the slot's pages, one call an
admission): device time of the program per call over the traced slice, in milliseconds. Nothing where the slice
holds no paste."""

from perf.layer_metrics import _common


def read(facts, trace, peak):
    if facts.get("kind") != "serving":
        return None
    measured = _common.program(trace, ["_paged_admit_impl"])
    if not measured:
        return None
    return 1000.0 * measured["seconds"] / measured["calls"]
