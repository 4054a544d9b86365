"""The paged-attention kernel's share of its roofline: the least time
the chip could take to read the live pool blocks' keys and values (at
the configuration's STATED compute dtype, whole blocks, the count kept
in the model's ``flops``) over the device time of the kernel's
operation in the trace.

The engine counts ``paged_blocks_live`` once a dispatch, for one
layer's call; a decode round is ``decode_chunk`` steps of one call a
layer at (to within a block a row) the same tables, so the traced
rounds' mean count, times the steps of a round, is scaled to the
``jit_decode`` programs the trace holds, as ``decode_step_roofline``
does. Valid where the kernel runs in the decode program only (no
chunked prefill); another program's calls would add device time and no
bytes, and the share would read low, never high. While the program's
pool is float32 the share cannot pass 50."""

KERNEL = "_paged_flash_attention_tpu_custom_call"
PROGRAM = "jit_decode"
COUNTER = "paged_blocks_live"


def read(obs):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    if obs["kind"] == "train_job" or trace is None or peaks is None:
        return None
    kernel_s = trace["ops"].get(KERNEL)
    prog = trace["programs"].get(PROGRAM)
    rounds = [r["counted"][COUNTER] for r in obs.get("traced_rounds", ())
              if r.get("counted", {}).get(COUNTER)]
    if not kernel_s or not prog or not rounds:
        return None
    cfg, fl = obs["cfg"], obs["flops"]
    dep = cfg["deployment"]
    live = sum(rounds) / len(rounds)
    step_bytes = fl.paged_live_bytes(
        cfg["n_embd"], cfg["n_layer"], dep["block_tokens"], live,
        cfg["compute_dtype"])
    step_flops = cfg["n_layer"] * fl.attention_forward_flops(
        cfg["n_embd"], 1, live * dep["block_tokens"])
    least = fl.roofline_seconds(step_flops, step_bytes, peaks)[0]
    return 100.0 * least * dep["decode_chunk"] * prog["count"] / kernel_s
