"""engine (serving/continuous.py): mean time a request waited for a slot, ``admission_started - submitted`` of the
life-cycle records whose first token fell in the window (the program's own stamps, host clock). With
``admission_mean_ms`` and ``front_ttft_gap_ms`` it adds up to the client's mean time to the first token."""

from perf.layer_metrics import _engine_log


def read(facts, trace, peak):
    return _engine_log.request_mean_ms(facts, "admission_started", "submitted")
