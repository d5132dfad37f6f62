"""device_ms_per_frame: device busy milliseconds in the traced window
(``bench.trace_reduce``) over the frames completed in it."""


def read(run):
    if run.system != "frame_stream" or run.trace is None or not run.frames:
        return None
    return 1e3 * run.trace["busy_s"] / run.frames
