"""frames_per_s: video frames completed in the window over the seconds
from the window's start to the last completion (host clock)."""


def read(run):
    if run.system != "frame_stream" or not run.frames or run.window_s <= 0:
        return None
    return run.frames / run.window_s
