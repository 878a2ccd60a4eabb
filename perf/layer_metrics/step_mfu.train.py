"""model step (train/driver.py): (6 x matrix parameters + attention) x trained positions / (window x chips x
bf16 peak), in percent; recomputation not counted. FLOPs from perf/work.py."""

from perf import work


def read(facts, trace, peak):
    if peak is None:  # no chip: no share of a peak
        return None
    if facts.get("kind") != "training":
        return None
    tokens = facts["steps_timed"] * facts["batch"] * facts["seq"]
    flops = tokens * work.encoder_train_flops_per_token(facts["config"], facts["seq"])
    return 100.0 * flops / (facts["window_s"] * facts["chips"] * peak["bf16_flops_per_s"])
