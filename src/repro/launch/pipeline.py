"""Pipeline parallelism (GPipe schedule) over a mesh axis via shard_map.

Demonstrates the PP feature claimed in DESIGN.md Sec. 5: layer groups are
sharded over a ``stage`` mesh axis (the natural choice at multi-pod scale
is the DCN-connected ``pod`` axis, since PP's point-to-point transfers are
the only collective that tolerates DCN latency), microbatches flow through
stages on a ring of ``jax.lax.ppermute`` transfers, and the classic
(P - 1)-bubble schedule emerges: tick t runs microbatch (t - stage) on
each stage.

This module is the *forward* pipeline (inference/prefill shape); it is
exercised by tests/test_pipeline.py which proves bit-level agreement with
the unpipelined stack, and its lowered HLO shows the collective-permute
chain (the dry-run evidence that the schedule is real). Training would
wrap it in the standard GPipe fwd/bwd interleave; recorded as future work
in EXPERIMENTS.md.

Note on emulation cost: under SPMD every stage executes every tick (idle
stages compute on masked data), so wall-clock on CPU does not show the
bubble -- the schedule, transfers and sharding are what this validates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.transformer import _run_stack


def pipeline_forward(cfg: ArchConfig, groups, h, mesh, *,
                     stage_axis: str = "stage", microbatches: int = 2):
    """Run the group stack pipelined over ``stage_axis``.

    groups: stacked group params [G, ...] with G % num_stages == 0;
    h: [B, S, D] embedded activations, B % microbatches == 0.
    Returns [B, S, D] identical (up to fp order) to the plain stack.
    """
    Pn = mesh.shape[stage_axis]
    M = microbatches
    B = h.shape[0]
    if B % M:
        raise ValueError("batch must divide microbatches")
    hs = h.reshape((M, B // M) + h.shape[1:])  # [M, b, S, D]

    def stage_fn(local_groups, hs_local):
        stage = jax.lax.axis_index(stage_axis)

        def run(x):  # no-cache full-sequence pass through local groups
            out, _, _ = _run_stack(cfg, local_groups, x, mode="train")
            return out

        total = M + Pn - 1
        perm = [(i, i + 1) for i in range(Pn - 1)]
        out_buf = jnp.zeros_like(hs_local)

        def tick(carry, t):
            h_prev, out_buf = carry
            mb = jnp.clip(t, 0, M - 1)
            x_in = jnp.where(stage == 0, hs_local[mb], h_prev)
            active = jnp.logical_and(t - stage >= 0, t - stage < M)
            y = run(x_in)
            y = jnp.where(active, y, x_in)
            # last stage banks its finished microbatch t - (Pn - 1)
            done_mb = jnp.clip(t - (Pn - 1), 0, M - 1)
            bank = jnp.logical_and(stage == Pn - 1,
                                   jnp.logical_and(t - (Pn - 1) >= 0,
                                                   t - (Pn - 1) < M))
            out_buf = jax.lax.dynamic_update_slice(
                out_buf,
                jnp.where(bank, y, jax.lax.dynamic_slice(
                    out_buf, (done_mb,) + (0,) * (out_buf.ndim - 1),
                    (1,) + out_buf.shape[1:])[0])[None],
                (done_mb,) + (0,) * (out_buf.ndim - 1))
            h_next = jax.lax.ppermute(y, stage_axis, perm)
            return (h_next, out_buf), None

        (h_last, out_buf), _ = jax.lax.scan(
            tick, (jnp.zeros_like(hs_local[0]), out_buf),
            jnp.arange(total))
        # broadcast the last stage's results to all stages (so the output
        # sharding is replicated over the stage axis, like the input)
        out_buf = jnp.where(stage == Pn - 1, out_buf,
                            jnp.zeros_like(out_buf))
        return jax.lax.psum(out_buf, stage_axis)

    fn = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(P(stage_axis), P()),  # groups sharded by stage; h repl.
        out_specs=P(),
        check_vma=False)
    out = fn(groups, hs)
    return out.reshape(h.shape)
