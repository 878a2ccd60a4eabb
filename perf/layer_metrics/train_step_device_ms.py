"""kernels (train step): device time of the train step's XLA program per execution, in milliseconds
(the trace's program with the most device time: the jitted trainer)."""


def read(facts, trace, peak):
    if facts.get("kind") != "training" or not trace or not trace["programs"]:
        return None
    name, entry = max(trace["programs"].items(), key=lambda kv: kv[1]["seconds"])
    return entry["seconds"] / entry["calls"] * 1e3 if entry["calls"] > 0 else None
