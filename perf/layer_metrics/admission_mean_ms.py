"""engine (serving/continuous.py): mean time from the start of a request's admission (slot and blocks assigned) to
its first token: prefill chunks, interleaved decode dispatches and the paste, ``first_token - admission_started`` of
the life-cycle records whose first token fell in the window (the program's own stamps, host clock)."""

from perf.layer_metrics import _engine_log


def read(facts, trace, peak):
    return _engine_log.request_mean_ms(facts, "first_token", "admission_started")
