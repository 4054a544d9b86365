"""The share of the device's busy time the ``lfm2_moe`` training step
spends in the grouped expert products, forward and transposed (the
calls ``lfm2_moe_grouped_train_roofline`` times), over the traced
stretch: how much of a step the routed experts are."""


def read(obs):
    trace, fl = obs.get("trace"), obs["flops"]
    if (obs["kind"] != "train_job" or trace is None
            or not hasattr(fl, "grouped_call_seconds")):
        return None
    seconds = fl.grouped_call_seconds(trace["ops"])
    if not seconds or not trace["busy_s"]:
        return None
    return 100.0 * seconds / trace["busy_s"]
