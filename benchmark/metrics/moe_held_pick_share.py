"""Share of routed (row, pick) pairs that fell on experts this chip
holds: the engine's ``moe_picks_held`` over ``moe_picks``, window's end
less window's start. With half the router's outputs held it reads ~50
by construction; it says the share was applied. ``None`` from an engine
that counts no picks (a net without experts, or a program from before
the counters)."""


def read(obs):
    if obs["kind"] == "train_job":
        return None
    before, after = obs["before"], obs["after"]
    picks = after.get("moe_picks", 0) - before.get("moe_picks", 0)
    if picks <= 0:
        return None
    return 100.0 * (after["moe_picks_held"]
                    - before["moe_picks_held"]) / picks
