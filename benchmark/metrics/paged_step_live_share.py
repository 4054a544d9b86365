"""Share of the steps the paged-attention kernel pays that score keys:
the compute blocks that hold a live entry (the engine's
``paged_blocks_walked`` over ``paged_blocks_per_step``, the pool blocks
of one compute block) over ``paged_steps_paid`` (one layer's call's
grid steps plus the trips of its loop over a row's compute blocks, for
a dispatch's tables), window's end less window's start. What is left
under 100 is steps that find nothing: an idle slot's grid step, an
unmapped compute block between live ones. ``None`` for a training cell,
from an engine that does not count the steps it pays (a program from
before the counter) and where no step was paid."""


def read(obs):
    if obs["kind"] == "train_job":
        return None
    before, after = obs["before"], obs["after"]
    if "paged_steps_paid" not in after:
        return None
    paid = after["paged_steps_paid"] - before["paged_steps_paid"]
    per_step = after.get("paged_blocks_per_step", 0)
    if paid <= 0 or per_step <= 0:
        return None
    walked = after["paged_blocks_walked"] - before["paged_blocks_walked"]
    return 100.0 * walked / per_step / paid
