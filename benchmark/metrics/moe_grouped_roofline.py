"""The grouped expert product's share of its roofline: the least time
the chip could take to read each touched expert's two matrices once (at
the configuration's stated dtype) and to do the held picks' operations,
over the device time of the Pallas grouped matmul in the trace (the
call carries the name of the function that makes it, ``gmm``).

The engine counts ``moe_experts_touched`` and ``moe_picks_held`` over
every program of a round, prefill included, and the kernel's time in
the trace holds every program too; the traced rounds' mean is scaled
to the ``jit_decode`` programs the trace holds, as the other roofline
shares are. A prefill's many-row tiles read an expert's weights once a
tile, which this count does not have: the share then reads low, never
high."""

KERNEL = "gmm_tpu_custom_call"
PROGRAM = "jit_decode"


def read(obs):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    if obs["kind"] == "train_job" or trace is None or peaks is None:
        return None
    kernel_s = trace["ops"].get(KERNEL)
    prog = trace["programs"].get(PROGRAM)
    rounds = [r["counted"] for r in obs.get("traced_rounds", ())
              if r.get("counted", {}).get("moe_experts_touched")]
    if not kernel_s or not prog or not rounds:
        return None
    cfg, fl = obs["cfg"], obs["flops"]
    touched = sum(c["moe_experts_touched"] for c in rounds) / len(rounds)
    picks = sum(c.get("moe_picks_held", 0) for c in rounds) / len(rounds)
    least = fl.roofline_seconds(fl.grouped_flops(cfg, picks),
                                fl.grouped_bytes(cfg, touched), peaks)[0]
    return 100.0 * least * prog["count"] / kernel_s
