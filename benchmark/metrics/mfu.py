"""Model FLOP/s utilization of the training job over the traced stretch
of the window: operations the forward and backward passes need per step
(6 * N_block + attention per token, no recomputation, embedding and head
left out) over the mean device time of a whole step program
(``jit_steps``), times the share of the stretch the device was busy,
over the chip's bf16 peak."""

PROGRAM = "jit_steps"


def read(obs):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    if obs["kind"] != "train_job" or trace is None or peaks is None:
        return None
    prog = trace["programs"].get(PROGRAM)
    if not prog or not prog["seconds"]:
        return None
    cfg, mix, fl = obs["cfg"], obs["mix"], obs["flops"]
    per_call = mix["scan_steps"] * mix["batch"] * mix["seq_len"] * \
        fl.train_flops_per_token(cfg["n_embd"], cfg["n_inner"],
                                 cfg["n_layer"], mix["seq_len"])
    busy_share = trace["busy_s"] / obs["trace_window_s"]
    return 100.0 * per_call * prog["count"] / prog["seconds"] * \
        busy_share / peaks["bf16_flops"]
