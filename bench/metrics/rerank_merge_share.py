"""rerank_merge_share: percent of pass 2's grid steps in the window that
merged their point block into the running top-k,
100 * taco_rerank_blocks_merged_total / taco_rerank_blocks_total (the
registry is reset as the window opens, so its snapshot at the close holds
the window's counts). None where pass 2 counts no steps: the jnp path."""


def read(run):
    steps = run.counters.get("taco_rerank_blocks_total", 0)
    if not steps:
        return None
    return 100.0 * run.counters.get("taco_rerank_blocks_merged_total", 0) / steps
