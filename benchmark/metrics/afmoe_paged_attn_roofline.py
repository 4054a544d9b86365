"""The paged-attention kernel's share of its roofline in a net of
several layer kinds: the least time the chip could take to read the
live pool blocks' keys and values of EACH kind (whole blocks at the
configuration's stated dtype, a kind's blocks times its layers:
``afmoe_flops.paged_live_bytes``) over the device time of the kernel's
operation in the trace.

The engine counts, by kind, the pool blocks one layer's call copies for
a dispatch's tables (``paged_blocks_live_w<window>``), and the part of
it that admissions' chunks counted (``prefill_paged_blocks_live_w...``):
a decode dispatch is ``decode_chunk`` steps of one call a layer at (to
within a block a row) the same tables, a prefill chunk one call a
layer. The traced rounds' mean is scaled to the ``jit_decode`` programs
the trace holds, as the other roofline shares are. ``None`` from a
program without the counters by kind."""

KERNEL = "_paged_flash_attention_tpu_custom_call"
PROGRAM = "jit_decode"
COUNTER = "paged_blocks_live_w"


def read(obs):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    if obs["kind"] == "train_job" or trace is None or peaks is None:
        return None
    fl = obs["flops"]
    kernel_s = trace["ops"].get(KERNEL)
    prog = trace["programs"].get(PROGRAM)
    rounds = [r["counted"] for r in obs.get("traced_rounds", ())
              if any(k.startswith(COUNTER) for k in r.get("counted", {}))]
    if (not kernel_s or not prog or not rounds
            or not hasattr(fl, "kind_windows")):
        return None
    cfg = obs["cfg"]
    dep = cfg["deployment"]
    live = {}
    for kind, window in fl.kind_windows(cfg).items():
        name = f"{COUNTER}{window}"
        prefill = sum(c.get("prefill_" + name, 0) for c in rounds)
        decode = sum(c.get(name, 0) for c in rounds) - prefill
        live[kind] = (decode * dep["decode_chunk"] + prefill) / len(rounds)
    least = fl.roofline_seconds(
        fl.paged_flops(cfg, dep["block_tokens"], live),
        fl.paged_live_bytes(cfg, dep["block_tokens"], live), peaks)[0]
    return 100.0 * least * prog["count"] / kernel_s
