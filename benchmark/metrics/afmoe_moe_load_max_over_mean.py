"""``moe_load_max_over_mean`` for the ``afmoe`` stack, whose
configuration states the held experts as the range ``experts_held``:
the fullest held expert's rows over the mean of all held experts' rows,
a layer and a step: held x ``moe_load_max`` / ``moe_picks_held``
(the engine sums both over layers and steps), window's end less
window's start. 1 is an even load. ``None`` for another model's
configuration and from an engine that counts no picks."""


def read(obs):
    if obs["kind"] == "train_job" or "experts_held" not in obs["cfg"]:
        return None
    before, after = obs["before"], obs["after"]
    held_picks = (after.get("moe_picks_held", 0)
                  - before.get("moe_picks_held", 0))
    if held_picks <= 0:
        return None
    lo, hi = obs["cfg"]["experts_held"]
    return (hi - lo) * (after["moe_load_max"]
                        - before["moe_load_max"]) / held_picks
