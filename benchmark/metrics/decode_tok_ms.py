"""Wall time of one decode step: the engine's summed decode-round wall
over the window, divided by the tokens a slot advanced in those rounds
(``decode_chunk`` a round)."""


def read(obs):
    if obs["kind"] == "train_job" or obs.get("peaks") is None:
        return None
    rounds = obs["after"]["chunks"] - obs["before"]["chunks"]
    if rounds <= 0:
        return None
    wall = obs["after"]["decode_time_s"] - obs["before"]["decode_time_s"]
    return 1000.0 * wall / (rounds * obs["cfg"]["deployment"][
        "decode_chunk"])
