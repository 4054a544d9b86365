"""Live slots over ``n_slots``, mean over the decode rounds of the
window (the engine's ``occupancy_sum`` over ``chunks``, window's end
less window's start)."""


def read(obs):
    if obs["kind"] == "train_job":
        return None
    rounds = obs["after"]["chunks"] - obs["before"]["chunks"]
    if rounds <= 0:
        return None
    return 100.0 * (obs["after"]["occupancy_sum"]
                    - obs["before"]["occupancy_sum"]) / rounds
