"""enqueue_ms.stream: mean milliseconds the render service spent
planning and enqueueing one chunk of the stream
(``ChunkStats.dispatch_s``)."""


def read(run):
    if run.system != "frame_stream" or not run.chunks:
        return None
    return 1e3 * sum(c["dispatch_s"] for c in run.chunks) / len(run.chunks)
