"""engine (serving/continuous.py): milliseconds an iteration of the window spent in the ``emit`` phase — the per-row loop
under the lock: tokens handed to the stream queues, log-probabilities, the latency and rate feeds, finishing rows —
that phase's seconds over the iteration records that start in the window, divided by their number (the program's own
spans, host clock)."""

from perf.layer_metrics import _engine_log


def read(facts, trace, peak):
    return _engine_log.phase_ms(facts, "emit")
