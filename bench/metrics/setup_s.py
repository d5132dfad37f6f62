"""setup_s: seconds from process start to the start of the window:
JAX's start, the compile cache, building the service, the warm-up."""


def read(run):
    return run.setup_s if run.setup_s > 0 else None
