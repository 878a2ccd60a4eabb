"""engine (serving/continuous.py over models/layers.py Attention's windowed decode read): the positions of the
pages the sliding layers' reads touched / the live rows' lengths, over the window's decode steps (program counters
stats()["window"]["window_positions_read"] and ["window_positions_live"], each summed over the sliding layers), in
percent: how much of a row a windowed layer still reads, which is what the window saves under the cell's mix
(a row shorter than the window reads its pages whole: a little over 100 %)."""


def read(facts, trace, peak):
    before, after = facts.get("before", {}), facts.get("after", {})
    if facts.get("kind") != "serving" or "window_positions_live" not in after:
        return None
    live = after["window_positions_live"] - before["window_positions_live"]
    if live <= 0:
        return None
    return 100.0 * (after["window_positions_read"] - before["window_positions_read"]) / live
