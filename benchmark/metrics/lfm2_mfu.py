"""Model FLOP/s utilization of the ``lfm2_moe`` training job over the
traced stretch of the window: the operations one step's forward and
backward passes need (``lfm2_moe_flops.train_flops_per_step``: the
mixers, the dense feed-forward, the routed experts' held pairs at their
expectation, the attention scores, the tied head; no recomputed
operation) over the mean device time of a whole step program
(``jit_steps``), times the share of the stretch the device was busy,
over the chip's bf16 peak. The share of the WHOLE step."""

PROGRAM = "jit_steps"


def read(obs):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    if obs["kind"] != "train_job" or trace is None or peaks is None:
        return None
    prog = trace["programs"].get(PROGRAM)
    count = getattr(obs["flops"], "train_flops_per_step", None)
    if not prog or not prog["seconds"] or count is None:
        return None
    per_call = obs["mix"]["scan_steps"] * count(obs["cfg"], obs["mix"])
    busy_share = trace["busy_s"] / obs["trace_window_s"]
    return 100.0 * per_call * prog["count"] / prog["seconds"] * \
        busy_share / peaks["bf16_flops"]
