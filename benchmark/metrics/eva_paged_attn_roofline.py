"""The paged-attention kernel's share of its roofline under EVA's two
calls a layer (the aligned window's exact keys, then the summaries of
every earlier window): the least time the chip could take to read the
cache entries the engine counted for the traced rounds' dispatches
(``eva_window_entries_read`` + ``eva_summary_entries_read``, one layer's,
times the layers, at the stated dtype) and to score the (query, entry)
pairs it counted (``eva_*_pairs_scored``: a decode step's one query an
entry, an admission chunk's every query under the causal edge), decode
dispatches and admission chunks alike (``evabyte_flops.paged_bytes`` /
``paged_flops``; a dispatch at a time, the larger of the two bounds),
over the device time of the kernel's operation in the trace. The traced
rounds' mean is scaled to the ``jit_decode`` programs the trace holds,
as the other roofline shares are. ``None`` from a program without the
counters."""

KERNEL = "_paged_flash_attention_tpu_custom_call"
PROGRAM = "jit_decode"
KINDS = ("window", "summary")


def read(obs):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    if obs["kind"] == "train_job" or trace is None or peaks is None:
        return None
    fl = obs["flops"]
    kernel_s = trace["ops"].get(KERNEL)
    prog = trace["programs"].get(PROGRAM)
    rounds = [r["counted"] for r in obs.get("traced_rounds", ())
              if "eva_window_entries_read" in r.get("counted", {})]
    if (not kernel_s or not prog or not rounds
            or not hasattr(fl, "paged_bytes")):
        return None
    cfg = obs["cfg"]
    least = 0.0
    for c in rounds:
        def both(what, prefix=""):
            return sum(c.get(f"{prefix}eva_{kind}_{what}", 0)
                       for kind in KINDS)

        parts = [(both("entries_read", "prefill_"),
                  both("pairs_scored", "prefill_"))]
        parts.append((both("entries_read") - parts[0][0],
                      both("pairs_scored") - parts[0][1]))
        for entries, pairs in parts:    # admission chunks, then decode
            least += fl.roofline_seconds(
                fl.paged_flops(cfg, pairs), fl.paged_bytes(cfg, entries),
                peaks)[0]
    return 100.0 * (least / len(rounds)) * prog["count"] / kernel_s
