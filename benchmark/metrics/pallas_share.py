"""Device time of the Pallas kernels (``tpu_custom_call`` operations:
the paged-attention kernel) over the device's busy time in the trace."""


def read(obs):
    trace = obs.get("trace")
    if obs["kind"] == "train_job" or trace is None or not trace["busy_s"]:
        return None
    kernel = sum(s for name, s in trace["ops"].items()
                 if "custom-call" in name or "custom_call" in name)
    return 100.0 * kernel / trace["busy_s"]
