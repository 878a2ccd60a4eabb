"""engine (serving/continuous.py): wall time of the longest pass of the engine loop that started in the window, in
milliseconds (the six phases of one iteration record summed) — the mark of the stall ROADMAP S12 names: 1.5-4 times
``engine_iteration_ms`` in a clean window, seconds in a stalled one, whose evidence the program keeps
(``GET /debug/engine``, ``slow_iterations_log``). Nothing on a program whose records do not name their waits: its slow
iterations kept no evidence (the program's own spans, host clock)."""

from perf.layer_metrics import _engine_log


def read(facts, trace, peak):
    found = _engine_log.window(facts)
    if found is None:
        return None
    iterations = found[0]
    if not all(hasattr(r, "wait_s") for r in iterations):
        return None
    return 1e3 * max(sum(r.phase_s) for r in iterations)
