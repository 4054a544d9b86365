"""Share of the paged-attention kernel's arithmetic spent on keys that
exist: the engine's ``paged_blocks_live`` (pool blocks a dispatch's
tables make one layer's kernel call copy) over ``paged_blocks_walked``
(the blocks' worth of keys it scores: whole compute blocks of
``paged_blocks_per_step`` pool blocks), window's end less window's
start. ``None`` from an engine that counts no walk (no paged pool, or a
program from before the counters)."""


def read(obs):
    if obs["kind"] == "train_job":
        return None
    before, after = obs["before"], obs["after"]
    if "paged_blocks_walked" not in after:
        return None
    walked = after["paged_blocks_walked"] - before["paged_blocks_walked"]
    if walked <= 0:
        return None
    return 100.0 * (after["paged_blocks_live"]
                    - before["paged_blocks_live"]) / walked
