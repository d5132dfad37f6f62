"""Record ``tiny_tpu.xplane.pb``: a small trace of a TPU with ``bench.*``
host spans, for ``test_trace_reduce.py``.

    python3 bench/tests/data/record_xplane.py <out.xplane.pb>

Run from the root of a checkout, on a machine with a TPU, so that the
trace names this script by its relative path.
"""

import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def main(out: str) -> None:
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    g = jax.jit(lambda x: jnp.cumsum(x * 2.0, axis=0))
    x = jnp.ones((1024, 1024))
    f(x).block_until_ready()
    g(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.serve"):
                with jax.profiler.TraceAnnotation("bench.dispatch_planned"):
                    y = f(x)
                with jax.profiler.TraceAnnotation("bench.finalize"):
                    y.block_until_ready()
            time.sleep(0.002)
            with jax.profiler.TraceAnnotation("bench.fetch"):
                np.asarray(g(x))
    jax.profiler.stop_trace()
    shutil.copy(next(Path(tmp).rglob("*.xplane.pb")), out)
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
