"""engine (serving/continuous.py): wall time of one pass of the engine loop, the six phases of the iteration records
that start in the window summed and divided by their number (the program's own spans, host clock)."""

from perf.layer_metrics import _engine_log


def read(facts, trace, peak):
    return _engine_log.phase_ms(facts)
