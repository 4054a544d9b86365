"""The ``afmoe`` stack's decode program's share of its roofline: the
least time the chip could take for a traced round's decode steps (the
larger of operations over peak FLOP/s and bytes over peak bytes/s, by
``afmoe_flops.decode_round`` from the configuration's shapes and what
the program counted: touched experts, held picks; contexts through
``RoundProbe``, a sliding layer reading at most its window of each)
over the device time of ``jit_decode`` in the trace. A round that also
finished an admission adds its prefill chunks to the same counters,
and under ``prefill_<name>`` too: the decode program's part of a round
is the difference. The traced rounds' mean is scaled to the programs
the trace holds."""

PROGRAM = "jit_decode"


def decode_counts(obs):
    """For each traced round, what its ONE decode dispatch counted:
    the round's counters less the part its prefill programs counted.
    Rounds from a program without the counters give nothing."""
    cfg, fl = obs["cfg"], obs["flops"]
    expert_layers = [f for _, f in fl.kinds(cfg)].count("experts")
    want = expert_layers * cfg["deployment"]["decode_chunk"]
    out = []
    for r in obs.get("traced_rounds", ()):
        c = r.get("counted", {})
        part = {k: c.get(k, 0) - c.get("prefill_" + k, 0)
                for k in ("moe_layer_steps", "moe_experts_touched",
                          "moe_picks_held")}
        if part["moe_layer_steps"] == want:
            out.append((r, part))
    return out


def read(obs):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    if obs["kind"] == "train_job" or trace is None or peaks is None:
        return None
    fl = obs["flops"]
    prog = trace["programs"].get(PROGRAM)
    if not prog or not prog["seconds"] or not hasattr(fl, "kind_windows"):
        return None
    rounds = decode_counts(obs)
    if not rounds:
        return None
    cfg = obs["cfg"]
    chunk = cfg["deployment"]["decode_chunk"]
    least = 0.0
    for r, c in rounds:
        reads = [max(t - chunk + j + 1, 1) for t in r["contexts"]
                 for j in range(chunk)]
        ctx = {kind: sum(min(n, window) for n in reads)
               for kind, window in fl.kind_windows(cfg).items()}
        nflops, nbytes = fl.decode_round(
            cfg, chunk, len(reads), ctx, c["moe_experts_touched"],
            c["moe_picks_held"])
        least += fl.roofline_seconds(nflops, nbytes, peaks)[0]
    return 100.0 * (least / len(rounds)) * prog["count"] / prog["seconds"]
