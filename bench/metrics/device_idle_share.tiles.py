"""device_idle_share.tiles: share of the traced window in which no
operation ran on the device (``bench.trace_reduce``), tile serving."""


def read(run):
    if run.system != "tile_server" or run.trace is None:
        return None
    return 100.0 * run.trace["idle_share"]
