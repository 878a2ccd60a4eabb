"""engine (serving/continuous.py): milliseconds an iteration of the window spent in the ``schedule`` phase —
cancellations, the waiting-queue sweep and the start of admissions (deadline sheds, tenant round robin, radix match,
block allocation), lock held, no device work — that phase's seconds over the iteration records that start in the
window, divided by their number (the program's own spans, host clock)."""

from perf.layer_metrics import _engine_log


def read(facts, trace, peak):
    return _engine_log.phase_ms(facts, "schedule")
