"""engine (serving/continuous.py): milliseconds an iteration of the window spent in the ``admit`` phase — admission steps
(chunk slicing, prefill and first-token dispatches, the gather of cached rows) and the paste into the pool, less the
time blocked on a device result — that phase's seconds over the iteration records that start in the window, divided by
their number (the program's own spans, host clock)."""

from perf.layer_metrics import _engine_log


def read(facts, trace, peak):
    return _engine_log.phase_ms(facts, "admit")
