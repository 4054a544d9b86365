"""Pool blocks held by slot tables and pending admissions over
``kv_blocks``, the highest reading after any round of the window.
Blocks that only the prefix trie retains are not live."""


def read(obs):
    if obs["kind"] == "train_job" or not obs.get("rounds"):
        return None
    return 100.0 * max(r["live_blocks"] for r in obs["rounds"]) / \
        obs["cfg"]["deployment"]["kv_blocks"]
