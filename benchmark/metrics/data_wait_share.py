"""Share of the window the trainer's loop spent waiting on its input
pipeline (host clock around the feed's ``next``)."""


def read(obs):
    if obs["kind"] != "train_job" or obs.get("peaks") is None:
        return None
    return 100.0 * obs["data_wait_s"] / obs["window_s"]
