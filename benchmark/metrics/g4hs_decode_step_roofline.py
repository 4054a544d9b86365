"""The hybrid stack's decode program's share of its roofline: the least
time the chip could take for a traced round's decode steps (the larger
of operations over peak FLOP/s and bytes over peak bytes/s, by
``granite_hybrid_flops.decode_round`` from the configuration's shapes
and what the program counted: touched experts, held picks, state rows;
contexts through ``RoundProbe``) over the device time of ``jit_decode``
in the trace. A round that also admitted a request adds its prefill to
the same counters, and under ``prefill_<name>`` too: the decode
program's part of a round is the difference. The traced rounds' mean
is scaled to the programs the trace holds."""

PROGRAM = "jit_decode"


def decode_counts(obs):
    """For each traced round, what its ONE decode dispatch counted:
    the round's counters less the part its prefill programs counted.
    Rounds from a program without the counters give nothing."""
    cfg = obs["cfg"]
    want = cfg["num_hidden_layers"] * cfg["deployment"]["decode_chunk"]
    out = []
    for r in obs.get("traced_rounds", ()):
        c = r.get("counted", {})
        part = {k: c.get(k, 0) - c.get("prefill_" + k, 0)
                for k in ("moe_layer_steps", "moe_experts_touched",
                          "moe_picks_held", "ssm_state_rows")}
        if part["moe_layer_steps"] == want:
            out.append((r, part))
    return out


def read(obs):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    if obs["kind"] == "train_job" or trace is None or peaks is None:
        return None
    prog = trace["programs"].get(PROGRAM)
    rounds = decode_counts(obs)
    if not prog or not prog["seconds"] or not rounds:
        return None
    cfg, fl = obs["cfg"], obs["flops"]
    chunk = cfg["deployment"]["decode_chunk"]
    n_mamba = fl.kinds(cfg).count("mamba")
    least = 0.0
    for r, c in rounds:
        ctx = sum(max(t - chunk + j + 1, 1) for t in r["contexts"]
                  for j in range(chunk))
        state_rows = c["ssm_state_rows"]
        nflops, nbytes = fl.decode_round(
            cfg, chunk, state_rows / max(n_mamba, 1), ctx,
            c["moe_experts_touched"], c["moe_picks_held"], state_rows)
        least += fl.roofline_seconds(nflops, nbytes, peaks)[0]
    return 100.0 * (least / len(rounds)) * prog["count"] / prog["seconds"]
