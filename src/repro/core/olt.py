"""Offset Lookup Tables (OLT) -- paper Sec. 5.2/5.3, adapted to TPU.

The paper compacts concurrent OLT insertions with an ``atomicAdd`` on a
global counter. TPUs have no global atomics; the paper itself (Sec. 5.3.1)
names the alternative we use: an exclusive prefix-sum over the subdivide
flags. On TPU this is deterministic (stable insertion order -- something the
atomic version does NOT guarantee) and maps onto the VPU.

Coordinates convention: a region at level ``l`` is identified by its integer
coordinate ``(cy, cx)`` in the level-l region grid (side ``g * r**l``).
Its pixel origin is ``(cy * s, cx * s)`` with ``s = n // (g * r**l)``.
A subdividing region (cy, cx) produces children ``(cy*r + dy, cx*r + dx)``
for ``dy, dx in [0, r)`` -- exactly the write-OLT entries of the paper.

Also provides the k-dimensional scalar OLT compaction of Sec. 7.2:
space-filling-curve encodings (canonical a.k.a. nested-loop order, and
Morton/Z-order) so one int32/int64 scalar replaces a k-vector.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "next_pow2",
    "pad_olt",
    "compact_ranks",
    "compact_gather",
    "subdivide_olt",
    "subdivide_olt_tagged",
    "ring_init",
    "ring_read",
    "ring_write",
    "sfc_canonical_encode",
    "sfc_canonical_decode",
    "morton_encode2d",
    "morton_decode2d",
    "morton_encode3d",
    "morton_decode3d",
]


def next_pow2(x: int) -> int:
    """Bucket size for serial-kernel relaunch (DESIGN.md Sec. 2): dynamic
    counts are rounded up to the next power of two so at most O(log n)
    distinct kernel shapes are ever compiled."""
    x = int(x)
    if x <= 1:
        return 1
    return 1 << (x - 1).bit_length()


def pad_olt(coords: jax.Array, count: int, capacity: int) -> Tuple[jax.Array, jax.Array]:
    """Pad an OLT of ``count`` live entries up to ``capacity`` rows.

    Returns (padded_coords [capacity, k], valid [capacity] bool). Padded
    rows replicate row 0 so downstream kernels never index out of bounds;
    ``valid`` masks them out.
    """
    if coords.ndim != 2:
        raise ValueError("coords must be [N, k]")
    n = coords.shape[0]
    if capacity < count:
        raise ValueError(f"capacity {capacity} < count {count}")
    if n >= capacity:
        out = coords[:capacity]
    else:
        fill = jnp.broadcast_to(coords[:1], (capacity - n, coords.shape[1]))
        out = jnp.concatenate([coords, fill], axis=0)
    valid = jnp.arange(capacity) < count
    return out, valid


# ---------------------------------------------------------------------------
# Double-buffered OLT ring (the ``run_ask_scan`` carry -- DESIGN: one
# read buffer + one write buffer of equal width, swapped by parity each
# level, so live-OLT memory is O(2 * max_level_capacity) instead of the
# fused engine's sum of per-level worst cases).
# ---------------------------------------------------------------------------

def ring_init(coords: jax.Array, count: int, capacity: int) -> jax.Array:
    """Build a [2, capacity, k] ring with ``coords`` in the front (parity-0)
    buffer. If ``capacity < count`` the tail is truncated (the caller is
    responsible for accounting those as overflow drops)."""
    buf0, _ = pad_olt(coords, min(count, capacity), capacity)
    return jnp.stack([buf0, jnp.zeros_like(buf0)], axis=0)


def ring_read(ring: jax.Array, parity: jax.Array, cap: int) -> jax.Array:
    """Live prefix of the front buffer: [cap, k]. ``cap`` is the static
    per-level capacity slice; ``parity`` may be traced."""
    front = jax.lax.dynamic_index_in_dim(ring, parity, axis=0, keepdims=False)
    return front[:cap]


def ring_write(ring: jax.Array, parity: jax.Array, buf: jax.Array) -> jax.Array:
    """Store ``buf`` (a compact child OLT, width <= ring width) into the
    BACK buffer (1 - parity), zero-padding to the ring width."""
    width = ring.shape[1]
    if buf.shape[0] > width:
        raise ValueError(f"child OLT {buf.shape[0]} exceeds ring width {width}")
    if buf.shape[0] < width:
        pad = jnp.zeros((width - buf.shape[0],) + buf.shape[1:], buf.dtype)
        buf = jnp.concatenate([buf, pad], axis=0)
    back = jnp.int32(1) - parity
    return jax.lax.dynamic_update_index_in_dim(ring, buf, back, axis=0)


@jax.jit
def compact_ranks(flags: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The atomicAdd replacement (paper Sec. 5.3.1).

    ``flags`` [N] bool: which entries insert. Returns
    ``ranks`` [N] int32 -- exclusive prefix sum (the slot each inserting
    entry owns; junk where flag is False) and ``count`` -- total inserts
    (the paper's final ``count`` variable == next kernel's grid size).
    """
    f = flags.astype(jnp.int32)
    inclusive = jnp.cumsum(f)
    ranks = inclusive - f  # exclusive scan
    count = inclusive[-1] if f.shape[0] > 0 else jnp.int32(0)
    return ranks.astype(jnp.int32), count.astype(jnp.int32)


@jax.jit
def batched_compact_ranks(flags: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-column compact ranks: ``flags`` [N, E] -> (ranks [N, E],
    counts [E]). Column e is an independent OLT -- this is the MoE
    token->expert dispatch primitive (DESIGN.md Sec. 4: the paper's
    atomicAdd-per-expert becomes E parallel prefix sums)."""
    f = flags.astype(jnp.int32)
    inc = jnp.cumsum(f, axis=0)
    return (inc - f).astype(jnp.int32), inc[-1].astype(jnp.int32)


def compact_gather(values: jax.Array, flags: jax.Array, capacity: int,
                   *, ranks_count=None) -> Tuple[jax.Array, jax.Array]:
    """Compact ``values[flags]`` into the first ``count`` rows of a
    [capacity, ...] array (write-OLT form). Deterministic/stable order.
    ``ranks_count`` optionally supplies a precomputed ``(ranks, count)``
    pair (e.g. from the policy-routed ``kernels.ops.compact_ranks``) so
    the scan is not recomputed -- every lowering of the exclusive scan is
    exact integer math, so the result is identical either way."""
    ranks, count = (compact_ranks(flags) if ranks_count is None
                    else ranks_count)
    out_shape = (capacity,) + values.shape[1:]
    out = jnp.zeros(out_shape, dtype=values.dtype)
    idx = jnp.where(flags, ranks, capacity)  # dropped rows scatter off the end
    out = out.at[idx].set(values, mode="drop")
    return out, count


@functools.partial(jax.jit, static_argnames=("r", "capacity"))
def subdivide_olt(
    coords: jax.Array, flags: jax.Array, *, r: int, capacity: int
) -> Tuple[jax.Array, jax.Array]:
    """One read-OLT -> write-OLT step (paper Sec. 5.3.2).

    Every flagged region (cy, cx) inserts its r*r children contiguously at
    ``rank * r * r`` -- identical layout to the paper's atomic scheme, but
    via prefix sum. Returns (child_coords [capacity, 2], child_count).
    The prefix sum runs in the ``ask.compact`` scope, which names it in
    the compiled program and in a profile.
    """
    with jax.named_scope("ask.compact"):
        ranks, count = compact_ranks(flags)
    R = r * r
    n = coords.shape[0]
    dy, dx = jnp.meshgrid(jnp.arange(r), jnp.arange(r), indexing="ij")
    offs = jnp.stack([dy.ravel(), dx.ravel()], axis=-1).astype(coords.dtype)  # [R, 2]
    children = coords[:, None, :] * r + offs[None, :, :]  # [N, R, 2]
    base = jnp.where(flags, ranks * R, capacity)  # off-end drop for unflagged
    idx = base[:, None] + jnp.arange(R)[None, :]  # [N, R]
    out = jnp.zeros((capacity, 2), dtype=coords.dtype)
    out = out.at[idx.reshape(-1)].set(children.reshape(-1, 2), mode="drop")
    return out, count * R


@functools.partial(jax.jit, static_argnames=("r", "capacity"))
def subdivide_olt_tagged(
    rows: jax.Array, flags: jax.Array, *, r: int, capacity: int,
    ranks_count=None,
) -> Tuple[jax.Array, jax.Array]:
    """Frame-tagged OLT step for the POOLED cross-frame worklist.

    ``rows`` is [N, 3] int32 ``(frame, cy, cx)`` -- one worklist holding
    regions from every frame of a dispatch. Subdivision multiplies only
    the coordinate columns by ``r``; the frame tag is carried into all
    r*r children unchanged. Insertion layout is identical to
    ``subdivide_olt`` (flagged parent at rank k owns slots
    ``[k*r*r, (k+1)*r*r)``), so because the pooled worklist keeps frames
    in stable frame-major order, each frame's subsequence of children is
    exactly what its private ``subdivide_olt`` would have produced.
    Returns (child_rows [capacity, 3], child_count). ``ranks_count``
    optionally supplies a precomputed ``(ranks, count)`` pair (see
    ``compact_gather``).
    """
    ranks, count = (compact_ranks(flags) if ranks_count is None
                    else ranks_count)
    R = r * r
    dy, dx = jnp.meshgrid(jnp.arange(r), jnp.arange(r), indexing="ij")
    offs = jnp.stack([jnp.zeros(R, jnp.int32), dy.ravel(), dx.ravel()],
                     axis=-1).astype(rows.dtype)  # [R, 3]; frame offset 0
    scale = jnp.asarray([1, r, r], dtype=rows.dtype)  # frame tag unscaled
    children = rows[:, None, :] * scale[None, None, :] + offs[None, :, :]
    base = jnp.where(flags, ranks * R, capacity)  # off-end drop for unflagged
    idx = base[:, None] + jnp.arange(R)[None, :]  # [N, R]
    out = jnp.zeros((capacity, 3), dtype=rows.dtype)
    out = out.at[idx.reshape(-1)].set(children.reshape(-1, 3), mode="drop")
    return out, count * R


@functools.partial(jax.jit, static_argnames=("k", "capacity"))
def subdivide_olt_scalar(codes: jax.Array, flags: jax.Array, *, k: int,
                         capacity: int) -> Tuple[jax.Array, jax.Array]:
    """k-dimensional OLT step with SCALAR (Morton) entries -- paper
    Sec. 7.2: one int32 per region instead of a k-vector (k-fold smaller
    OLT). For r = 2 the Morton child codes are just
    ``(code << k) | j, j in [0, 2^k)`` -- no decode needed.
    Returns (child_codes [capacity], child_count)."""
    ranks, count = compact_ranks(flags)
    R = 1 << k
    children = (codes.astype(jnp.uint32)[:, None] << k) | jnp.arange(
        R, dtype=jnp.uint32)[None, :]
    base = jnp.where(flags, ranks * R, capacity)
    idx = base[:, None] + jnp.arange(R)[None, :]
    out = jnp.zeros((capacity,), dtype=jnp.uint32)
    out = out.at[idx.reshape(-1)].set(children.reshape(-1), mode="drop")
    return out, count * R


# ---------------------------------------------------------------------------
# Space-filling curves (paper Sec. 7.2) -- scalar OLT entries for k >= 3
# ---------------------------------------------------------------------------

def sfc_canonical_encode(p: jax.Array, grid: Tuple[int, ...]) -> jax.Array:
    """Eq. (33): canonical (nested-loop) order. ``p`` is [..., k] with
    p[..., d] in [0, grid[d]); returns [...] scalars."""
    k = len(grid)
    if p.shape[-1] != k:
        raise ValueError("coordinate dim mismatch")
    out = jnp.zeros(p.shape[:-1], dtype=jnp.int64)
    stride = 1
    for d in range(k):  # d = 0 is fastest-varying (x), matching Eq. (31)
        out = out + p[..., d].astype(jnp.int64) * stride
        stride *= int(grid[d])
    return out


def sfc_canonical_decode(s: jax.Array, grid: Tuple[int, ...]) -> jax.Array:
    """Inverse of Eq. (33)."""
    s = s.astype(jnp.int64)
    parts = []
    for d in range(len(grid)):
        parts.append((s % int(grid[d])).astype(jnp.int32))
        s = s // int(grid[d])
    return jnp.stack(parts, axis=-1)


def _part1by1(x: jax.Array) -> jax.Array:
    """Spread the low 16 bits of x so there is a 0 bit between each."""
    x = x.astype(jnp.uint32) & jnp.uint32(0x0000FFFF)
    x = (x | (x << 8)) & jnp.uint32(0x00FF00FF)
    x = (x | (x << 4)) & jnp.uint32(0x0F0F0F0F)
    x = (x | (x << 2)) & jnp.uint32(0x33333333)
    x = (x | (x << 1)) & jnp.uint32(0x55555555)
    return x


def _compact1by1(x: jax.Array) -> jax.Array:
    x = x.astype(jnp.uint32) & jnp.uint32(0x55555555)
    x = (x | (x >> 1)) & jnp.uint32(0x33333333)
    x = (x | (x >> 2)) & jnp.uint32(0x0F0F0F0F)
    x = (x | (x >> 4)) & jnp.uint32(0x00FF00FF)
    x = (x | (x >> 8)) & jnp.uint32(0x0000FFFF)
    return x


def morton_encode2d(p: jax.Array) -> jax.Array:
    """Z-order scalar for [..., 2] coords (y, x), 16 bits per axis."""
    y = _part1by1(p[..., 0])
    x = _part1by1(p[..., 1])
    return ((y << 1) | x).astype(jnp.uint32)


def morton_decode2d(s: jax.Array) -> jax.Array:
    s = s.astype(jnp.uint32)
    x = _compact1by1(s)
    y = _compact1by1(s >> 1)
    return jnp.stack([y, x], axis=-1).astype(jnp.int32)


def _part1by2(x: jax.Array) -> jax.Array:
    x = x.astype(jnp.uint32) & jnp.uint32(0x000003FF)
    x = (x | (x << 16)) & jnp.uint32(0x030000FF)
    x = (x | (x << 8)) & jnp.uint32(0x0300F00F)
    x = (x | (x << 4)) & jnp.uint32(0x030C30C3)
    x = (x | (x << 2)) & jnp.uint32(0x09249249)
    return x


def _compact1by2(x: jax.Array) -> jax.Array:
    x = x.astype(jnp.uint32) & jnp.uint32(0x09249249)
    x = (x | (x >> 2)) & jnp.uint32(0x030C30C3)
    x = (x | (x >> 4)) & jnp.uint32(0x0300F00F)
    x = (x | (x >> 8)) & jnp.uint32(0x030000FF)
    x = (x | (x >> 16)) & jnp.uint32(0x000003FF)
    return x


def morton_encode3d(p: jax.Array) -> jax.Array:
    """Z-order scalar for [..., 3] coords (z, y, x), 10 bits per axis."""
    z = _part1by2(p[..., 0])
    y = _part1by2(p[..., 1])
    x = _part1by2(p[..., 2])
    return ((z << 2) | (y << 1) | x).astype(jnp.uint32)


def morton_decode3d(s: jax.Array) -> jax.Array:
    s = s.astype(jnp.uint32)
    x = _compact1by2(s)
    y = _compact1by2(s >> 1)
    z = _compact1by2(s >> 2)
    return jnp.stack([z, y, x], axis=-1).astype(jnp.int32)
