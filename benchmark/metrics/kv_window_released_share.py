"""Of the block-layers the live contexts span (a context's blocks times
the layers that cache it), the share NOT held because a window layer
released them: the engine's ``kv_blocks_spanned_w<window>`` less
``kv_blocks_held_w<window>`` of each layer kind, weighted by the kind's
layers, over the spanned block-layers of all kinds, window's end less
window's start (both are summed over rounds, as ``occupancy_sum`` is).
0 while no context passes the sliding window. ``None`` for a training
cell and from a program that does not count by kind (one kind of
layer, or a program from before the counters)."""


def read(obs):
    if obs["kind"] == "train_job":
        return None
    fl = obs["flops"]
    if not hasattr(fl, "kind_windows"):
        return None
    before, after = obs["before"], obs["after"]
    spanned = released = 0.0
    for kind, window in fl.kind_windows(obs["cfg"]).items():
        span, held = (f"kv_blocks_spanned_w{window}",
                      f"kv_blocks_held_w{window}")
        if span not in after:
            return None
        layers = fl.layers_of(obs["cfg"], kind)
        s = after[span] - before.get(span, 0)
        spanned += layers * s
        released += layers * (s - (after[held] - before.get(held, 0)))
    return 100.0 * released / spanned if spanned > 0 else None
