"""``paged_step_live_share`` (its reader says what it counts) in a cell
of the ``afmoe`` stack: the engine sums ``paged_blocks_walked`` and
``paged_steps_paid`` over the layer KINDS (one layer's kernel call of
each, decode dispatches and admission chunks alike), and every kind's
compute block holds the same ``paged_blocks_per_step`` pool blocks (the
kinds differ in window, not in heads), so the share is that reader's
arithmetic over both kinds' calls. ``None`` for another model's counts
and wherever that reader finds nothing."""

from benchmark import common


def read(obs):
    if (obs["kind"] == "train_job"
            or not hasattr(obs["flops"], "kind_windows")):
        return None
    return common.load_reader("paged_step_live_share")(obs)
