"""enqueue_ms.tiles: mean milliseconds the render service spent planning
and enqueueing one miss batch (``ChunkStats.dispatch_s``)."""


def read(run):
    if run.system != "tile_server" or not run.chunks:
        return None
    return 1e3 * sum(c["dispatch_s"] for c in run.chunks) / len(run.chunks)
