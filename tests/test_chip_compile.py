"""Compile the served programs for a TPU v5e chip that is described, not
attached: the pooled chunk program, the per-frame ``ask_scan`` batch
program and the exhaustive reference, at the chip smoke's size
(n = 4096, 8 frames per chunk). What the TPU compiler refuses here would
fail on the chip; each program must also fit the chip's 16 GB of HBM,
and none may carry a Pallas kernel (the served path runs the jnp
lowering).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

HBM_BYTES = 16 * 10**9  # one v5e chip (Google Cloud, "TPU v5e")
N, FRAMES = 4096, 8
LEAF_CAP = 44059  # the leaf ring ``pooled_capacities`` plans at P = 0.7


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def frames_mesh(topo):
    """The one-device ``frames`` mesh ``RenderService`` builds."""
    return Mesh(np.array(topo.devices[:1]), ("frames",),
                axis_types=(AxisType.Auto,))


@pytest.fixture(scope="module")
def problem():
    from repro.workloads import FrameProblem

    return FrameProblem(n=N, g=4, r=2, B=32, max_dwell=512)


def _check(compiled, label, canvas=None):
    """Bytes the program needs on the chip. With ``canvas`` (its HLO
    shape, e.g. ``s32[32768,4096]``), also count whole-canvas copies:
    at most one, outside the region loops (a fill lowered as a windowed
    scatter copied the canvas twice per region)."""
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{label}: {total} bytes > {HBM_BYTES}"
    text = compiled.as_text()
    assert "tpu_custom_call" not in text, (
        f"{label}: a Pallas kernel reached the served program")
    if canvas is not None:
        copies = re.findall(re.escape(canvas) + r"\{[^}]*\} copy\(", text)
        assert len(copies) <= 1, f"{label}: {len(copies)} canvas copies"
    return total


@pytest.fixture(scope="module")
def pooled_compiled(problem, frames_mesh):
    from repro.core.pooled import _jitted_pooled, pooled_capacities

    caps = pooled_capacities(problem, (0.7,) * FRAMES)
    assert caps[-1] == LEAF_CAP
    fn = _jitted_pooled(problem, caps, FRAMES, mesh=frames_mesh)
    return fn.lower(
        jax.ShapeDtypeStruct((1, FRAMES, 4), jnp.float32),
        jax.ShapeDtypeStruct((1, FRAMES), jnp.bool_)).compile()


def test_pooled_chunk_program_compiles(pooled_compiled):
    # the output alone is 8 canvases of 64 MiB
    assert (_check(pooled_compiled, "pooled", f"s32[{FRAMES * N},{N}]")
            >= FRAMES * N * N * 4)


def test_pooled_escape_loop_carries_lane_dense_planes(pooled_compiled,
                                                      problem):
    """Leaf dwell A's escape loop (the ``while`` in the body of the
    block loop under ``ask.dwell``) streams its carry through memory on
    every trip. Planes of ``[rows, 32, 32]`` tile as (8, 128) and pad
    each to four times its bytes; the loop must carry them 128 lanes
    wide."""
    loops = [line for line in pooled_compiled.as_text().splitlines()
             if re.search(r"= \(.*\) while\(.*op_name=\"[^\"]*ask\.dwell/"
                          r"while/body/while\"", line)]
    assert len(loops) == 1, loops
    carry = loops[0].split(" while(")[0]
    arrays = [(dtype, [int(d) for d in dims.split(",")])
              for dtype, dims in re.findall(r"\b(\w+)\[([\d,]+)\]", carry)]
    assert not [a for a in arrays if a[1][-1] == problem.B], carry
    planes = [a for a in arrays if len(a[1]) >= 2]
    assert all(dims[-1] == 128 for _, dims in planes), carry
    pixels = max(int(np.prod(dims)) for _, dims in planes)
    assert pixels % (problem.B * problem.B) == 0
    # z's real and imaginary parts and the dwell, each over every leaf pixel
    held = [dtype for dtype, dims in planes if int(np.prod(dims)) == pixels]
    assert held.count("f32") >= 2 and held.count("s32") >= 1, carry


def test_pooled_dwell_loops_over_live_blocks(pooled_compiled, problem):
    """A runs block by block up to the leaf count: the ``while`` under
    ``ask.dwell`` stops at a bound it carries (the block count, read
    from the live leaf count at run time), not at a constant, and the
    escape loop in its body covers ``dwell_block_rows`` rows a trip."""
    from repro.kernels import ops

    text = pooled_compiled.as_text()
    outer = re.search(r"= \(.*\) while\(.*condition=(%[\w.]+),.*"
                      r"op_name=\"[^\"]*ask\.dwell/while\"", text)
    assert outer, "no block loop under ask.dwell"
    cond = re.search(r"^" + re.escape(outer.group(1)) + r" .*?^}", text,
                     re.M | re.S).group(0)
    assert "compare(" in cond and "constant(" not in cond, cond
    inner = re.search(r"= \((.*)\) while\(.*op_name=\"[^\"]*ask\.dwell/"
                      r"while/body/while\"", text)
    rows = ops.dwell_block_rows(problem.B, LEAF_CAP)
    assert rows < LEAF_CAP
    assert f"[{rows},8,128]" in inner.group(1), inner.group(1)


def test_ask_scan_batch_program_compiles(problem, frames_mesh):
    from repro.core.ask import _jitted_pipeline, scan_capacities

    caps = scan_capacities(N, 4, 2, 32)
    fn = _jitted_pipeline(problem, caps, batched=True, mesh=frames_mesh)
    compiled = fn.lower(
        jax.ShapeDtypeStruct((FRAMES, 4), jnp.float32)).compile()
    assert (_check(compiled, "ask_scan", f"s32[{FRAMES},{N},{N}]")
            >= FRAMES * N * N * 4)


def test_exhaustive_program_compiles(problem, one_chip):
    from repro.kernels import ref

    window = tuple(jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
                   for _ in range(4))
    compiled = ref.mandelbrot_ref.lower(
        N, window, max_dwell=problem.max_dwell,
        workload=problem.workload).compile()
    assert _check(compiled, "exhaustive") >= N * N * 4
