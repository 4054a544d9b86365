"""The one-step state-space update's share of its roofline: the least
time the chip could take to read and write each live row's recurrent
state (float32, ``granite_hybrid_flops.ssm_step_bytes``) and to do the
update's operations, over the device time of the Pallas kernel
``_ssm_step_update`` in the trace. The kernel runs in the decode
program only (a prefill runs the chunked scan), so the rows are the
decode program's part of each traced round's count, and their mean is
scaled to the ``jit_decode`` programs the trace holds."""

from benchmark.common import load_by_path

KERNEL = "_ssm_step_update_tpu_custom_call"
PROGRAM = "jit_decode"


def read(obs):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    if obs["kind"] == "train_job" or trace is None or peaks is None:
        return None
    kernel_s = trace["ops"].get(KERNEL)
    prog = trace["programs"].get(PROGRAM)
    # the same rounds, the same parts, as the decode program's share
    decode_counts = load_by_path(
        "metrics", "g4hs_decode_step_roofline").decode_counts
    rounds = [c["ssm_state_rows"] for _, c in decode_counts(obs)]
    if not kernel_s or not prog or not rounds or not sum(rounds):
        return None
    cfg, fl = obs["cfg"], obs["flops"]
    rows = sum(rounds) / len(rounds)
    least = fl.roofline_seconds(fl.ssm_step_flops(cfg, rows),
                                fl.ssm_step_bytes(cfg, rows), peaks)[0]
    return 100.0 * least * prog["count"] / kernel_s
