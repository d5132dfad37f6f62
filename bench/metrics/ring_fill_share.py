"""ring_fill_share: live regions entering each level of the scan (the
leaf level too), over the ring rows the scan allocated for those levels,
summed over the window's chunks (``ASKStats.region_counts``,
``frame_leaf_counts`` and ``olt_caps``). The pooled engine's
capacities are per device shard; the per-frame engine's per frame."""


def read(run):
    if run.system != "frame_stream" or not run.chunks:
        return None
    live = alloc = 0
    for c in run.chunks:
        per = c["devices"] if c["engine"] == "ask_pooled" else c["frames"]
        live += c["live"]
        alloc += sum(c["caps"]) * per
    return 100.0 * live / alloc if alloc else None
