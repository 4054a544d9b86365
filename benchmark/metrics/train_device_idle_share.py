"""Share of the traced stretch of the training window in which no
operation ran on the device."""


def read(obs):
    trace = obs.get("trace")
    if obs["kind"] != "train_job" or trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / obs["trace_window_s"])
