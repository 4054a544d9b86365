"""The ``evabyte`` stack's decode program's share of its roofline: the
least time the chip could take for a traced round's decode steps (the
larger of operations over peak FLOP/s and bytes over peak bytes/s, by
``evabyte_flops.decode_round``: every layer's weights and head 0 once a
step, and what the engine counted for the dispatch: the exact keys of
each live row's aligned window and the summaries of every window before
it, a layer, and the summary entries the steps wrote) over the device
time of ``jit_decode`` in the trace. A round that also ran admission
chunks adds them to the same counters, and under ``prefill_<name>``
too: the decode program's part of a round is the difference. The traced
rounds' mean is scaled to the programs the trace holds. ``None`` from a
program without the counters."""

PROGRAM = "jit_decode"
ENTRIES = ("eva_window_entries_read", "eva_summary_entries_read")
WRITTEN = "eva_summaries_written"


def decode_part(counted: dict, name: str) -> float:
    return counted.get(name, 0) - counted.get("prefill_" + name, 0)


def read(obs):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    if obs["kind"] == "train_job" or trace is None or peaks is None:
        return None
    fl = obs["flops"]
    prog = trace["programs"].get(PROGRAM)
    rounds = [r for r in obs.get("traced_rounds", ())
              if ENTRIES[0] in r.get("counted", {})]
    if (not prog or not prog["seconds"] or not rounds
            or not hasattr(fl, "decode_round")):
        return None
    cfg = obs["cfg"]
    chunk = cfg["deployment"]["decode_chunk"]
    least = 0.0
    for r in rounds:
        c = r["counted"]
        entries = sum(decode_part(c, name) for name in ENTRIES)
        nflops, nbytes = fl.decode_round(
            cfg, chunk, len(r["contexts"]) * chunk, entries,
            decode_part(c, WRITTEN))
        least += fl.roofline_seconds(nflops, nbytes, peaks)[0]
    return 100.0 * (least / len(rounds)) * prog["count"] / prog["seconds"]
