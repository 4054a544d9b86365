"""The TRAINED grouped expert product's share of its roofline: the
least time the chip could take for the held (token, pick) pairs' three
products forward and the two transposes of each, in every expert layer
of every traced step (the larger of their operations over the bf16 peak
and their bytes over the HBM peak, ``lfm2_moe_flops``), over the device
time of the grouped-product custom calls in the trace, forward and
transposed (``gmm``, which also computes the rows' cotangents, and
``tgmm``, the weights').

The held pairs are taken AT THEIR EXPECTATION (a training cell's
readers are handed no program counters); a step's own count lies
within a few percent of it (PERF.md). Where the program recomputes a
layer on the way back, the forward products run twice and are counted
once: the share then reads low, never high."""

PROGRAM = "jit_steps"


def read(obs):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    fl = obs["flops"]
    if (obs["kind"] != "train_job" or trace is None or peaks is None
            or not hasattr(fl, "grouped_call_seconds")):
        return None
    seconds = fl.grouped_call_seconds(trace["ops"])
    prog = trace["programs"].get(PROGRAM)
    if not seconds or not prog:
        return None
    cfg, mix = obs["cfg"], obs["mix"]
    pairs = fl.held_pairs_per_step(cfg, mix)
    least = fl.roofline_seconds(fl.grouped_train_flops(cfg, pairs),
                                fl.grouped_train_bytes(cfg, pairs),
                                peaks)[0]
    steps = mix["scan_steps"] * prog["count"]
    return 100.0 * least * fl.expert_layers(cfg) * steps / seconds
