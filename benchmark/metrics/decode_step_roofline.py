"""The decode program's share of its roofline: the least time the chip
could take for the traced rounds' decode steps (the larger of operations
over peak FLOP/s and bytes over peak bytes/s, from the configuration's
shapes at its STATED compute dtype, live rows only) over the device time
of ``jit_decode`` in the trace."""

PROGRAM = "jit_decode"


def read(obs):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    if obs["kind"] == "train_job" or trace is None or peaks is None:
        return None
    prog = trace["programs"].get(PROGRAM)
    rounds = [r for r in obs.get("traced_rounds", ()) if r["contexts"]]
    if not prog or not prog["seconds"] or not rounds:
        return None
    cfg, fl = obs["cfg"], obs["flops"]
    chunk = cfg["deployment"]["decode_chunk"]
    shape = (cfg["n_embd"], cfg["n_inner"], cfg["n_layer"])
    least = 0.0
    for r in rounds:
        for j in range(chunk):   # the cache grows by one a step
            ctx = [max(c - chunk + j + 1, 1) for c in r["contexts"]]
            least += fl.roofline_seconds(
                fl.decode_step_flops(*shape, ctx),
                fl.decode_step_bytes(*shape, ctx, cfg["compute_dtype"]),
                peaks)[0]
    return 100.0 * (least / len(rounds)) * prog["count"] / prog["seconds"]
