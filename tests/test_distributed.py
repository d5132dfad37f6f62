"""Distributed-path integration tests. Each runs in a subprocess with 8
placeholder devices (XLA locks the device count at first init, so the main
test process -- which must see 1 device for the smoke tests -- cannot host
these)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


def _run(code: str, timeout=420, devices=8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=ROOT)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_sharded_solve_batch_bit_identical():
    """The ISSUE acceptance case: on 8 host devices, solve_batch(...,
    mesh=...) is bit-identical to the unsharded run_ask_scan_batch for
    F in {1, 7, 8, 16} (padding masked), stats sums match, one dispatch,
    and divisible batches actually land sharded across all 8 devices."""
    out = _run("""
        import numpy as np
        import jax.numpy as jnp
        from repro.core.ask import run_ask_scan_batch
        from repro.launch.mesh import make_frames_mesh
        from repro.mandelbrot import MandelbrotProblem, solve_batch

        prob = MandelbrotProblem(n=128, g=4, r=2, B=16, max_dwell=32,
                                 backend="jnp")
        mesh = make_frames_mesh()
        assert int(mesh.devices.size) == 8
        for F in (1, 7, 8, 16):
            b = np.stack([[-1.6 + 0.02 * i, -1.1, 0.55, 1.05]
                          for i in range(F)]).astype(np.float32)
            ref, st_ref = run_ask_scan_batch(prob, jnp.asarray(b),
                                             safety_factor=1e9)
            shd, st = solve_batch(prob, b, mesh=mesh, safety_factor=1e9)
            assert shd.shape == (F, 128, 128)
            np.testing.assert_array_equal(np.asarray(shd), np.asarray(ref))
            assert st.kernel_launches == 1
            assert st.leaf_count == st_ref.leaf_count
            assert st.overflow_dropped == st_ref.overflow_dropped == 0
            assert st.region_counts == st_ref.region_counts
            if F % 8 == 0:  # no ragged slice: output stays frame-sharded
                assert len(shd.sharding.device_set) == 8, shd.sharding
        print("OK")
    """)
    assert "OK" in out


def test_render_service_chunked_streaming():
    """launch.render_service on an 8-device mesh: 19 frames through chunk
    size 8 -> 3 chunks, ONE dispatch each (the padded tail reuses the same
    compiled program), concatenated output bit-identical to one unsharded
    batch over all 19 frames."""
    out = _run("""
        import numpy as np
        import jax.numpy as jnp
        from repro.core.ask import run_ask_scan_batch
        from repro.launch.mesh import make_frames_mesh
        from repro.launch.render_service import RenderService, zoom_bounds
        from repro.mandelbrot import MandelbrotProblem

        prob = MandelbrotProblem(n=128, g=4, r=2, B=16, max_dwell=32,
                                 backend="jnp")
        svc = RenderService(prob, mesh=make_frames_mesh(), chunk_frames=8,
                            safety_factor=1e9)
        bounds = list(zoom_bounds(19))
        canvases, rs = svc.render(bounds)
        assert canvases.shape == (19, 128, 128)
        assert rs.frames == 19 and rs.chunks == 3
        assert rs.dispatches == 3 and rs.dispatches_per_chunk == 1.0
        # the ragged 3-frame tail must NOT have retraced the chunk program
        assert rs.program_traces in (None, 1), rs.program_traces
        ref, st_ref = run_ask_scan_batch(
            prob, jnp.asarray(np.asarray(bounds, np.float32)),
            safety_factor=1e9)
        np.testing.assert_array_equal(canvases, np.asarray(ref))
        assert rs.leaf_count == st_ref.leaf_count
        assert rs.overflow_dropped == st_ref.overflow_dropped == 0
        print("OK")
    """)
    assert "OK" in out


def test_render_service_pipelined_sharded():
    """The async double-buffered service on an 8-device mesh: depth-3
    pipelining keeps the in-flight queue bounded, preserves one dispatch
    per chunk, and stays bit-identical to the synchronous stream."""
    out = _run("""
        import numpy as np
        from repro.launch.mesh import make_frames_mesh
        from repro.launch.render_service import RenderService, zoom_bounds
        from repro.mandelbrot import MandelbrotProblem

        prob = MandelbrotProblem(n=128, g=4, r=2, B=16, max_dwell=32,
                                 backend="jnp")
        mesh = make_frames_mesh()
        assert int(mesh.devices.size) == 8
        sync_svc = RenderService(prob, mesh=mesh, chunk_frames=8,
                                 pipeline_depth=1, safety_factor=1e9)
        pipe_svc = RenderService(prob, mesh=mesh, chunk_frames=8,
                                 pipeline_depth=3, safety_factor=1e9)
        bounds = list(zoom_bounds(27))
        sync, rs_sync = sync_svc.render(bounds)
        pipe, rs_pipe = pipe_svc.render(bounds)
        np.testing.assert_array_equal(pipe, sync)
        assert pipe.shape == (27, 128, 128)
        for rs in (rs_sync, rs_pipe):
            assert rs.chunks == 4 and rs.dispatches_per_chunk == 1.0
            assert rs.program_traces in (None, 1), rs.program_traces
            assert rs.overflow_dropped == 0
        inflight = [c.in_flight for c in rs_pipe.chunk_stats]
        assert max(inflight) == 3 and min(inflight) >= 1
        print("OK")
    """)
    assert "OK" in out


def test_render_service_feedback_sharded():
    """The closed-loop feedback path on an 8-device mesh: per-chunk
    re-planned capacities compose with frame-axis sharding -- canvases
    stay bit-identical to the unsharded worst-case batch, chunk 0 plans
    from the prior, later chunks from measurement, zero drops, and
    every dispatch width stays a multiple of the device count."""
    out = _run("""
        import numpy as np
        import jax.numpy as jnp
        from repro.core.ask import run_ask_scan_batch
        from repro.launch.mesh import make_frames_mesh
        from repro.launch.render_service import RenderService, zoom_bounds
        from repro.mandelbrot import MandelbrotProblem

        prob = MandelbrotProblem(n=128, g=4, r=2, B=16, max_dwell=32,
                                 backend="jnp")
        mesh = make_frames_mesh()
        assert int(mesh.devices.size) == 8
        bounds = list(zoom_bounds(24, center=(-0.7436447860, 0.1318252536),
                                  width0=6.0, zoom_per_frame=1.02))
        svc = RenderService(prob, mesh=mesh, chunk_frames=8, feedback=True,
                            safety_factor=1.1)
        canvases, rs = svc.render(bounds)
        assert canvases.shape == (24, 128, 128)
        assert rs.overflow_dropped == 0
        assert rs.chunk_stats[0].p_source == "prior"
        assert any(c.p_source == "measured" for c in rs.chunk_stats[1:])
        for _key, width, caps in svc._used_sigs:
            assert width % 8 == 0, (width, caps)
        ref, _ = run_ask_scan_batch(
            prob, jnp.asarray(np.asarray(bounds, np.float32)),
            safety_factor=1e9)
        np.testing.assert_array_equal(canvases, np.asarray(ref))
        print("OK")
    """)
    assert "OK" in out


def test_small_mesh_dryrun_train_and_decode():
    """run_cell compiles a reduced arch on a 2x4 mesh for train + decode,
    exercising sharding rules end to end (incl. MoE/EP + MLA)."""
    out = _run("""
        import dataclasses, json
        import jax
        from repro.configs import get_config
        from repro.configs.shapes import ShapeCase
        from repro.launch.dryrun import run_cell
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        for arch in ("qwen3-4b", "deepseek-v2-lite-16b"):
            cfg = get_config(arch).reduced()
            cfg = dataclasses.replace(cfg, num_heads=8, num_kv_heads=4,
                                      vocab_pad_multiple=64)
            for case in (ShapeCase("t", "train", 32, 8),
                         ShapeCase("d", "decode", 64, 8)):
                rec = run_cell(cfg, case, mesh)
                assert rec["status"] == "ok", rec.get("error")
                print(arch, case.kind, rec["memory"]["peak_per_device_bytes"],
                      rec["collectives"]["total_bytes"])
        print("OK")
    """)
    assert "OK" in out


def test_train_crash_resume_and_elastic_mesh():
    """Fault tolerance end to end: crash mid-run, auto-resume from the
    checkpoint, finish on a DIFFERENT mesh (elastic restart)."""
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        code = f"""
        import subprocess, sys, json
        from pathlib import Path
        args = [sys.executable, "-m", "repro.launch.train",
                "--arch", "qwen3-4b", "--reduced", "--steps", "8",
                "--seq-len", "32", "--global-batch", "4",
                "--ckpt-dir", {td!r}, "--ckpt-every", "2",
                "--log-every", "1", "--seed", "1"]
        # first run crashes at step 5 on a 2x4 mesh
        r = subprocess.run(args + ["--mesh", "2x4", "--crash-at-step", "5"],
                           capture_output=True, text=True)
        assert r.returncode != 0 and "injected crash" in (r.stderr + r.stdout)
        # resume on a DIFFERENT mesh (4x2) and finish
        r = subprocess.run(args + ["--mesh", "4x2"],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        assert "[resume] restoring step 4" in r.stdout, r.stdout
        assert "final loss" in r.stdout
        print("OK")
        """
        out = _run(code, timeout=560)
        assert "OK" in out


def test_grad_compression_trains():
    out = _run("""
        import subprocess, sys
        r = subprocess.run([sys.executable, "-m", "repro.launch.train",
            "--arch", "qwen3-4b", "--reduced", "--steps", "4",
            "--seq-len", "32", "--global-batch", "4", "--mesh", "2x4",
            "--compress-grads", "--microbatch", "2", "--log-every", "1"],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        assert "final loss" in r.stdout
        print("OK")
    """, timeout=560)
    assert "OK" in out


def test_multi_pod_mesh_axes():
    out = _run("""
        from repro.launch.mesh import make_production_mesh, data_axes
        import jax
        m = make_production_mesh(multi_pod=False)
        assert m.axis_names == ("data", "model") and m.devices.size == 256
        m2 = make_production_mesh(multi_pod=True)
        assert m2.axis_names == ("pod", "data", "model")
        assert m2.devices.size == 512
        assert data_axes(m2) == ("pod", "data")
        print("OK")
    """, devices=512)
    assert "OK" in out


def test_split_model_mesh_2d_tp():
    """2-D TP split mesh: head-misaligned archs (whisper-like) shard heads
    on model_a and the leftover axis lands on the weight's other dim."""
    out = _run("""
        import dataclasses
        from repro.configs import get_config
        from repro.configs.shapes import ShapeCase
        from repro.launch.dryrun import run_cell
        from repro.launch.mesh import make_mesh
        from repro.launch import sharding as sh
        mesh = make_mesh((2, 2, 2), ("data", "model_a", "model_b"))
        cfg = get_config("whisper-large-v3").reduced()
        cfg = dataclasses.replace(cfg, num_heads=6, num_kv_heads=6,
                                  vocab_pad_multiple=64)  # 6 % 4 != 0
        pol = sh.ShardingPolicy.for_arch(cfg, mesh)
        assert pol.model == ("model_a", "model_b")
        m, rest = pol.heads_split(mesh, 6)
        assert m == ("model_a",) and rest == ("model_b",)
        rec = run_cell(cfg, ShapeCase("t", "train", 32, 8), mesh)
        assert rec["status"] == "ok", rec.get("error")
        print("OK")
    """)
    assert "OK" in out


def test_sharded_pooled_bit_identical():
    """The pooled engine on 8 host devices: pooling happens WITHIN each
    device's shard (frame-major assignment, dead padding masked), so
    every ragged F must stay bit-identical to the unsharded pool AND to
    the per-frame scan engine, with one launch and zero drops. The
    pad_to contract (multiple of the device count) fails loudly."""
    out = _run("""
        import numpy as np
        import jax.numpy as jnp
        from repro.core.ask import run_ask_scan_batch
        from repro.core.pooled import (run_ask_pooled_batch,
                                       run_ask_pooled_sharded)
        from repro.launch.mesh import make_frames_mesh
        from repro.mandelbrot import MandelbrotProblem, solve_batch
        from repro.workloads import EngineOptions

        prob = MandelbrotProblem(n=128, g=4, r=2, B=16, max_dwell=32,
                                 backend="jnp")
        mesh = make_frames_mesh()
        assert int(mesh.devices.size) == 8

        def window(cx, cy, w):
            return (cx - w / 2, cy - w / 2, cx + w / 2, cy + w / 2)

        for F in (1, 7, 8, 16):
            # heterogeneous: sparse overviews + a deep seahorse tail
            b = np.stack(
                [window(-0.5, 0.0, 16.0 - i) for i in range(max(1, F - 2))]
                + [window(-0.7436447860, 0.1318252536, 3.0 / 2 ** (4 + k))
                   for k in range(min(2, F - 1))]).astype(np.float32)[:F]
            ref, st_ref = run_ask_scan_batch(prob, jnp.asarray(b),
                                             safety_factor=1e9)
            pool, st_pool = run_ask_pooled_batch(prob, b, safety_factor=1e9)
            shd, st = run_ask_pooled_sharded(prob, b, mesh=mesh,
                                             safety_factor=1e9)
            assert shd.shape == (F, 128, 128)
            np.testing.assert_array_equal(np.asarray(shd), np.asarray(ref))
            np.testing.assert_array_equal(np.asarray(pool), np.asarray(ref))
            assert st.kernel_launches == 1
            assert st.overflow_dropped == 0
            assert st.frame_leaf_counts == st_ref.frame_leaf_counts
            assert st.region_counts == st_ref.region_counts
            # the options= route lands on the same sharded pool
            via, st_via = solve_batch(
                prob, b, options=EngineOptions(engine="ask_pooled",
                                               mesh=mesh,
                                               safety_factor=1e9))
            np.testing.assert_array_equal(np.asarray(via), np.asarray(ref))
            assert st_via.kernel_launches == 1
        try:
            run_ask_pooled_sharded(prob, b, mesh=mesh, pad_to=9,
                                   safety_factor=1e9)
        except ValueError as e:
            assert "multiple" in str(e), e
        else:
            raise AssertionError("pad_to=9 on 8 devices must fail")
        print("OK")
    """)
    assert "OK" in out


def test_render_service_pooled_sharded():
    """Pooled serving on 8 devices: a heterogeneous feedback stream
    (chunked at workload switches only) stays bit-identical to the
    worst-case per-frame service, with the pooled ring accounted per
    device and zero drops after retries."""
    out = _run("""
        import numpy as np
        from repro.launch.mesh import make_frames_mesh
        from repro.launch.render_service import RenderService, zoom_bounds
        from repro.mandelbrot import MandelbrotProblem

        prob = MandelbrotProblem(n=128, g=4, r=2, B=16, max_dwell=32,
                                 backend="jnp")
        mesh = make_frames_mesh()
        bounds = list(zoom_bounds(19))
        ref, _ = RenderService(prob, mesh=mesh, chunk_frames=8,
                               safety_factor=1e9).render(bounds)
        svc = RenderService(prob, engine="ask_pooled", mesh=mesh,
                            chunk_frames=8, feedback=True,
                            safety_factor=1.2)
        canv, rs = svc.render(bounds)
        np.testing.assert_array_equal(canv, ref)
        assert rs.frames == 19 and rs.chunks == 3
        assert rs.overflow_dropped == 0
        # ONE shared ring per device shard: 8 * 2 * max(caps) + retries
        assert all(c.ring_rows >= 8 * 2 for c in rs.chunk_stats)
        print("OK")
    """)
    assert "OK" in out


def test_pooled_shards_dwell_their_own_live_blocks():
    """On 8 host devices each shard's A stops after the block holding
    its own last live leaf row: shards with different live counts run
    ``ceil(count / R) * R`` rows each (``ASKStats.dwell_rows``), their
    canvases equal the one-device pool's, and the render service's
    ``ChunkStats.shard_dwell_rows`` lines up with ``shard_leaf_counts``.
    The block is cut to 8 rows so the leaf rings span several."""
    out = _run("""
        import numpy as np
        from repro.core.pooled import (run_ask_pooled_batch,
                                       run_ask_pooled_sharded)
        from repro.kernels import ops
        from repro.launch.mesh import make_frames_mesh
        from repro.launch.render_service import RenderService
        from repro.workloads import FrameProblem

        R = 8
        prob = FrameProblem(n=128, g=4, r=2, B=16, max_dwell=32)
        ops.DWELL_BLOCK_PIXELS = R * prob.B * prob.B
        mesh = make_frames_mesh()

        def window(cx, cy, w):
            return (cx - w / 2, cy - w / 2, cx + w / 2, cy + w / 2)

        # two frames a shard, from a wide overview to a deep boundary zoom
        b = np.stack([window(-0.7436447860, 0.1318252536, 4.0 / 1.6 ** k)
                      for k in range(16)]).astype(np.float32)
        one, st_one = run_ask_pooled_batch(prob, b, safety_factor=1e9)
        shd, st = run_ask_pooled_sharded(prob, b, mesh=mesh,
                                         safety_factor=1e9)
        np.testing.assert_array_equal(np.asarray(shd), np.asarray(one))
        assert st.frame_leaf_counts == st_one.frame_leaf_counts
        leaves = [sum(st.frame_leaf_counts[2 * d:2 * d + 2])
                  for d in range(8)]
        assert len(set(leaves)) > 2, leaves
        assert R < st.olt_caps[-1]
        assert ops.dwell_block_rows(prob.B, st.olt_caps[-1]) == R
        assert st.dwell_rows == tuple(-(-c // R) * R for c in leaves)
        assert st_one.dwell_rows == (-(-st_one.leaf_count // R) * R,)

        svc = RenderService(prob, engine="ask_pooled", mesh=mesh,
                            chunk_frames=16, safety_factor=1e9)
        for r in svc.stream_chunks(list(b)):
            c = r.chunk
            R_c = ops.dwell_block_rows(prob.B, r.stats.olt_caps[-1])
            assert c.shard_dwell_rows == tuple(
                -(-x // R_c) * R_c for x in c.shard_leaf_counts), c
            np.testing.assert_array_equal(np.asarray(r.canvases),
                                          np.asarray(one))
        print("OK")
    """)
    assert "OK" in out


# The four-chip zoom-video host (``zoom4k_x4``): pooled feedback serving
# on a 4-device ``frames`` mesh, 4 frames a device, fed a video dealt
# serpentine so that each chunk lists its frames in rising depth.
_ZOOM_HOST = """
    import numpy as np
    from repro.launch.mesh import make_frames_mesh
    from repro.kernels import ops
    from repro.launch.render_service import RenderService, zoom_bounds
    from repro.workloads import FrameProblem, exhaustive

    prob = FrameProblem(n=256, g=4, r=2, B=32, max_dwell=64)
    DEVICES, FRAMES, CHUNK = {devices}, {frames}, 16

    def serpentine(num, chunk):
        # chunk c takes frame c of the video's first stretch, the
        # stretch's last-but-c of the second, c of the third, ...
        k = -(-num // chunk)
        order = []
        for c in range(k):
            order += [j * k + (c if j % 2 == 0 else k - 1 - c)
                      for j in range(chunk)]
        return [i for i in order if i < num]

    video = list(zoom_bounds(FRAMES, zoom_per_frame=1.2))
    bounds = [video[i] for i in serpentine(FRAMES, CHUNK)]

    def serve(devices):
        svc = RenderService(prob, mesh=make_frames_mesh(devices),
                            chunk_frames=CHUNK, engine="ask_pooled",
                            feedback=True)
        canv, rs = svc.render(bounds)
        return svc, canv, rs

    svc, canv, rs = serve(DEVICES)
    assert svc.chunk_frames == CHUNK and rs.overflow_dropped == 0
    # the counters against the frames' own leaf counts, chunk by chunk
    unretried = 0
    for r in svc.stream_chunks(bounds):
        c, leaves = r.chunk, r.stats.frame_leaf_counts
        assert len(c.shard_leaf_counts) == len(c.shard_frames) == DEVICES
        assert sum(c.shard_frames) == c.frames
        assert sum(c.shard_leaf_counts) == sum(leaves) == r.stats.leaf_count
        per = svc._pad_width(c.frames) // DEVICES
        for d in range(DEVICES):
            mine = range(d * per, min((d + 1) * per, c.frames))
            assert c.shard_frames[d] == len(mine), (c.shard_frames, per)
            assert c.shard_leaf_counts[d] == sum(leaves[j] for j in mine)
        if c.retries == 0:  # the dwell counter is the chunk's own dispatch
            unretried += 1
            R = ops.dwell_block_rows(prob.B, r.stats.olt_caps[-1])
            assert c.shard_dwell_rows == tuple(
                -(-x // R) * R for x in c.shard_leaf_counts), c
    assert unretried
    if DEVICES > 1:
        _, one, _ = serve(1)
        np.testing.assert_array_equal(canv, one)
    for b, got in zip(bounds, canv):
        ex, _ = exhaustive(prob.n, max_dwell=prob.max_dwell, bounds=b)
        np.testing.assert_array_equal(got, np.asarray(ex))
    print("OK")
"""


@pytest.mark.parametrize("devices,frames", [(4, 32), (4, 37), (1, 37)])
def test_zoom_host_shards(devices, frames):
    """The four-chip zoom host's shape at n = 256: pooled feedback
    serving over a serpentine-dealt zoom, 16-frame chunks. Four devices
    render what one renders, bit for bit, and what the exhaustive
    render gives (no border-filled pixel differs at this size);
    ``ChunkStats.shard_leaf_counts`` and ``shard_frames`` split each
    chunk's ``frame_leaf_counts`` frame-major over the devices, padding
    excluded (37 frames leave a 5-frame chunk padded to 8); on one
    device each is a 1-tuple."""
    out = _run(_ZOOM_HOST.format(devices=devices, frames=frames),
               devices=devices)
    assert "OK" in out
