"""engine (serving/continuous.py): milliseconds an iteration of the window spent in the ``fetch`` phase — every wait of
the engine thread for a device result: the decode dispatch's tokens, log-probabilities and done flags, an admission's
first token — that phase's seconds over the iteration records that start in the window, divided by their number (the
program's own spans, host clock)."""

from perf.layer_metrics import _engine_log


def read(facts, trace, peak):
    return _engine_log.phase_ms(facts, "fetch")
