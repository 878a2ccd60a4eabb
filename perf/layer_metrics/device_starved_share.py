"""device: seconds the device had nothing of the engine's to run (``starved_s``: from the moment the engine thread saw
its newest output ready to its next dispatch) / the wall time of the iteration records that start in the window, in
percent — the whole window's lower bound of the device's idle share, beside the traced slice's
``device_idle_share.serve``: the device may have run dry before the host looked, never after. Nothing on a program
whose records do not hold the field (the program's own spans, host clock)."""

from perf.layer_metrics import _engine_log


def read(facts, trace, peak):
    found = _engine_log.window(facts)
    if found is None:
        return None
    iterations = found[0]
    if not all(hasattr(r, "starved_s") for r in iterations):
        return None
    wall = sum(sum(r.phase_s) for r in iterations)
    return 100.0 * sum(sum(r.starved_s) for r in iterations) / wall if wall > 0 else None
