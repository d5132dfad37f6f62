"""speedup_vs_plain: seconds per frame of the plain exhaustive render
(``bench.reference``, each frame to ``block_until_ready``, after its
compile) over the engine's seconds per frame in the window (window time
over frames completed), both by the host clock: the paper's Fig. 8
ratio of ASK over Ex, served."""


def read(run):
    if (run.system != "frame_stream" or not run.frames
            or run.reference_s_per_answer is None):
        return None
    return run.reference_s_per_answer / (run.window_s / run.frames)
