"""engine (serving/continuous.py chunked admission over models/layers.py LatentAttention): key positions the
window's prefill chunks causally needed (each chunk's last position + 1, a layer) / key positions their reads
covered, masked or not (the whole row cache, a layer), in percent: program counters
stats()["latent"]["latent_positions_needed"] and ["latent_positions_attended"]. What a chunk that reads only the
row it needs (PERF.md, S5) would raise to 100."""


def read(facts, trace, peak):
    before, after = facts.get("before", {}), facts.get("after", {})
    if facts.get("kind") != "serving" or "latent_positions_attended" not in after:
        return None
    attended = after["latent_positions_attended"] - before["latent_positions_attended"]
    if attended <= 0:
        return None
    return 100.0 * (after["latent_positions_needed"] - before["latent_positions_needed"]) / attended
