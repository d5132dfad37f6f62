"""device_idle_share.stream: share of the traced window in which no
operation ran on the device (``bench.trace_reduce``), frame streams."""


def read(run):
    if run.system != "frame_stream" or run.trace is None:
        return None
    return 100.0 * run.trace["idle_share"]
