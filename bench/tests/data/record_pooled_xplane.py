"""Record ``tiny_pooled.xplane.pb.gz``: a trace of two chunks of a
pooled ``RenderService`` stream (n = 256, B = 32, 2 frames a chunk,
closed loop, two in flight) on a TPU, for ``test_stages.py``. The
harness's spans are around the stream as ``bench.systems.FrameStream``
puts them; the program's own ``repro.*`` spans and ``ask.*`` scopes
are inside.

    python3 bench/tests/data/record_pooled_xplane.py <out.xplane.pb.gz>

Run from the root of a checkout, on a machine with a TPU. The trace
keeps the HLO modules (the ``op_name`` of loops is only there), leaves
out Python's function events and the runtime's host events, and is
written gzipped, which keeps it under 300 KB.
"""

import gzip
import shutil
import sys
import tempfile
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT / "src"))

from repro.launch.mesh import make_frames_mesh  # noqa: E402
from repro.launch.render_service import (RenderService,  # noqa: E402
                                         zoom_bounds)
from repro.workloads import FrameProblem  # noqa: E402


def main(out: str) -> None:
    prob = FrameProblem(n=256, g=4, r=2, B=32, max_dwell=64)
    # worst-case rings on every chunk: one program, compiled before the
    # trace starts
    svc = RenderService(prob, mesh=make_frames_mesh(1), chunk_frames=2,
                        engine="ask_pooled", feedback=True,
                        safety_factor=1e9)
    frames = list(zoom_bounds(6))
    for result in svc.stream_chunks(frames[:2]):
        np.asarray(result.canvases)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1  # the annotations, not the runtime
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        it = svc.stream_chunks(frames[2:])
        while True:
            with jax.profiler.TraceAnnotation("bench.stream_next"):
                result = next(it, None)
            if result is None:
                break
            with jax.profiler.TraceAnnotation("bench.fetch"):
                np.asarray(result.canvases)
    jax.profiler.stop_trace()
    with open(next(Path(tmp).rglob("*.xplane.pb")), "rb") as src, \
            gzip.open(out, "wb", compresslevel=9) as dst:
        shutil.copyfileobj(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {Path(out).stat().st_size} bytes")


if __name__ == "__main__":
    main(sys.argv[1])
