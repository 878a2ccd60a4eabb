"""engine (serving/continuous.py): decoded rows / decode dispatches over the window (program counters)."""


def read(facts, trace, peak):
    if facts.get("kind") != "serving":
        return None
    before, after = facts["before"], facts["after"]
    dispatches = after["decode_dispatches"] - before["decode_dispatches"]
    return (after["decoded_rows"] - before["decoded_rows"]) / dispatches if dispatches > 0 else None
