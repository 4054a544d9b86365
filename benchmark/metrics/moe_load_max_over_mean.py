"""How uneven the routing is: the fullest held expert's rows over the
mean of all held experts' rows, a layer and a step. The engine sums
``moe_load_max`` and ``moe_picks_held`` over layers and steps, so the
mean of the ratio's numerator over that of its denominator is
held x ``moe_load_max`` / ``moe_picks_held``, window's end less
window's start. 1 is an even load. ``None`` from an engine that counts
no picks."""


def read(obs):
    if obs["kind"] == "train_job":
        return None
    before, after = obs["before"], obs["after"]
    held_picks = (after.get("moe_picks_held", 0)
                  - before.get("moe_picks_held", 0))
    if held_picks <= 0:
        return None
    held = obs["cfg"]["num_local_experts"]
    return held * (after["moe_load_max"]
                   - before["moe_load_max"]) / held_picks
