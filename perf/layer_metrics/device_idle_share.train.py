"""device: 1 - (union of the device's operation intervals / traced window), in percent, from the trace."""

from perf.layer_metrics import _common


def read(facts, trace, peak):
    return _common.idle_share(facts, trace, "training")
