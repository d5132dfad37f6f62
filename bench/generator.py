"""The one traffic generator. A traffic mix is a data file of
parameters (``bench/traffic/<mix>.json``); its ``pattern`` picks which
of the shapes below it fills in, and ``--seed`` draws the rest.

* ``frame_stream``: a zoom video, rendered closed loop. The video's
  frames are dealt to chunks so that every chunk spans the depth range
  (``serpentine``: chunk c takes frame c of the first stretch of the
  video, frame C-1-c of the second, c of the third, ...), so chunks
  carry alike work. The seed picks the chunk the stream starts at and
  jitters the zoom centre by a few of the deepest frame's pixels.
* ``viewport_sessions``: users of a slippy map, open loop. Sessions
  start at fixed times (a Poisson process drawn once from the mix's
  ``base_seed``); each looks at a point of interest chosen by Zipf
  popularity and pans or zooms one viewport at a time. The seed deals
  the sessions' scripts to the start times, so every seed offers the
  same arrivals and the same sessions in another order.

Nothing here touches JAX or the program under test.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Tuple

import numpy as np

__all__ = ["FramePlan", "Request", "ViewportPlan", "frame_plan",
           "viewport_plan", "points_of_interest", "tile_window",
           "viewport_tiles", "viewport_window"]

Window = Tuple[float, float, float, float]


# -- frame streams -----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FramePlan:
    """Frames in the order the stream offers them: ``frames[i]`` is the
    video index of the i-th frame offered and ``windows[i]`` its plane
    window. ``warmup`` frames come first and run before the window."""

    frames: Tuple[int, ...]
    windows: Tuple[Window, ...]
    warmup: int
    chunk: int


def _deal(num_frames: int, chunk: int, dealing: str) -> List[List[int]]:
    stretches = -(-num_frames // chunk)  # chunks in one pass of the video
    if dealing != "serpentine":
        raise ValueError(f"unknown dealing {dealing!r}")
    out = []
    for c in range(stretches):
        ks = [j * stretches + (c if j % 2 == 0 else stretches - 1 - c)
              for j in range(chunk)]
        out.append([k for k in ks if k < num_frames])
    return out


def frame_plan(config: dict, traffic: dict, seed: int, *, chunk: int,
               passes: int = 2) -> FramePlan:
    """Warm-up chunks, then ``passes`` passes of the dealt video."""
    rng = np.random.default_rng(seed)
    z = config["zoom"]
    num = int(config["frames"])
    per = float(z["per_frame"])
    deepest_px = float(z["width0"]) / (config["n"] * per ** (num - 1))
    jit = float(traffic.get("jitter_px", 0)) * deepest_px
    cx = float(z["center"][0]) + rng.uniform(-jit, jit)
    cy = float(z["center"][1]) + rng.uniform(-jit, jit)
    halves = []
    half = float(z["width0"]) / 2.0
    for _ in range(num):
        halves.append(half)
        half /= per
    chunks = _deal(num, chunk, traffic.get("dealing", "serpentine"))
    start = int(rng.integers(len(chunks)))
    warm = int(traffic.get("warmup_chunks", 1))
    order = []
    for i in range(warm + passes * len(chunks)):
        order += chunks[(start - warm + i) % len(chunks)]
    windows = tuple((cx - halves[k], cy - halves[k], cx + halves[k],
                     cy + halves[k]) for k in order)
    warm_frames = sum(len(chunks[(start - warm + i) % len(chunks)])
                      for i in range(warm))
    return FramePlan(frames=tuple(order), windows=windows,
                     warmup=warm_frames, chunk=chunk)


# -- slippy-map sessions -----------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Request:
    """One viewport, one tile wide at zoom ``z``, whose lower-left corner
    sits at ``(ox, oy)`` in tiles of that zoom (never on a tile edge, so
    it overlaps exactly 2 x 2 tiles). ``due`` is seconds from the start
    of the window; warm-up requests have ``due < 0``."""

    due: float
    session: int
    z: int
    ox: float
    oy: float


@dataclasses.dataclass(frozen=True)
class ViewportPlan:
    requests: Tuple[Request, ...]  # in due order, before the window first
    warmup_requests: int = 0

    @property
    def warmup(self) -> Tuple[Request, ...]:
        """The last requests due before the window: set-up serves them
        as fast as it can, to fill the cache as users would have."""
        before = [r for r in self.requests if r.due < 0]
        return tuple(before[len(before) - self.warmup_requests:])

    @property
    def window(self) -> Tuple[Request, ...]:
        return tuple(r for r in self.requests if r.due >= 0)


def points_of_interest(config: dict, count: int, seed: int) -> np.ndarray:
    """``count`` plane points near the set's boundary (escape dwell in
    [32, max_dwell)), drawn from ``seed`` over the tile pyramid's
    window: where people look at a fractal map."""
    re0, im0, re1, im1 = config["window"]
    rng = np.random.default_rng(seed)
    found = []
    while sum(len(f) for f in found) < count:
        cr = rng.uniform(re0, re1, 4096)
        ci = rng.uniform(im0, im1, 4096)
        zr, zi = cr.copy(), ci.copy()
        dwell = np.zeros(cr.shape, np.int64)
        for _ in range(int(config["max_dwell"])):
            live = zr * zr + zi * zi < 4.0
            zr, zi = (np.where(live, zr * zr - zi * zi + cr, zr),
                      np.where(live, 2.0 * zr * zi + ci, zi))
            dwell += live
        keep = (dwell >= 32) & (dwell < int(config["max_dwell"]))
        found.append(np.stack([cr[keep], ci[keep]], axis=1))
    return np.concatenate(found)[:count]


_GRID = 16  # viewport corners sit on 1/16 of a tile, offset by 1/32


def _snap(u: float) -> float:
    return (math.floor(u * _GRID) + 0.5) / _GRID


def viewport_plan(config: dict, traffic: dict, seed: int,
                  seconds: float) -> ViewportPlan:
    base = np.random.default_rng(int(traffic["base_seed"]))
    rng = np.random.default_rng(seed)
    rate = float(traffic["rate_per_s"])
    per_session = float(traffic["session_requests_mean"])
    # session start times: one Poisson process, fixed by the mix, begun
    # ``lead_s`` before the window so that the window opens on as many
    # sessions as it keeps
    warm_s = float(traffic["lead_s"])
    starts = []
    t = -warm_s
    while True:
        t += base.exponential(per_session / rate)
        if t >= seconds:
            break
        starts.append(t)
    zmin, zmax = (int(x) for x in traffic["start_zoom"])
    lo, hi = int(config["min_zoom"]), int(config["max_zoom"])
    shares = traffic["shares"]
    actions = list(shares)
    probs = np.array([float(shares[a]) for a in actions])
    probs /= probs.sum()
    pois = points_of_interest(config, int(traffic["pois"]),
                              int(traffic["poi_seed"]))
    ranks = np.arange(len(pois))
    weights = 1.0 / (ranks + 1.0) ** float(traffic["zipf_exponent"])
    weights /= weights.sum()
    # each start time keeps its own session length and think times, so
    # the requests fall due at the same times whatever the seed
    gaps = []
    for _ in starts:
        length = int(base.geometric(1.0 / per_session))
        gaps.append(base.exponential(float(traffic["think_s"]),
                                     size=length - 1))
    longest = max((len(g) for g in gaps), default=0)
    # what each session does, drawn once from the mix: the rank of its
    # point of interest, its start zoom, its moves; the seed deals these
    # scripts to the start times
    scripts = [(int(base.choice(ranks, p=weights)),
                int(base.integers(zmin, zmax + 1)),
                base.choice(len(actions), size=longest, p=probs),
                base.integers(0, 4, size=longest)) for _ in starts]
    # which point holds which rank, and when the ranks rotate: the mix's
    poi_of = base.permutation(len(pois))
    rotate_s = float(traffic["rotate_s"])
    step = int(traffic["rotate_ranks"])
    phase = base.uniform(0.0, rotate_s)
    order = rng.permutation(len(scripts))
    re0, im0, re1, im1 = config["window"]
    out = []
    for sess, (t0, k) in enumerate(zip(starts, order)):
        rank, z, moves, dirs = scripts[k]
        shift = int(math.floor((t0 + warm_s + phase) / rotate_s)) * step
        cr, ci = pois[poi_of[(rank + shift) % len(pois)]]
        tiles = 2 ** z
        cx = (cr - re0) / (re1 - re0) * tiles
        cy = (ci - im0) / (im1 - im0) * tiles
        ox, oy = _snap(cx - 0.5), _snap(cy - 0.5)
        due = t0
        out.append(Request(due, sess, z, ox, oy))
        for move, d, gap in zip(moves, dirs, gaps[sess]):
            due += float(gap)
            move = actions[int(move)]
            if move == "zoom_in" and z < hi:
                z, ox, oy = z + 1, _snap(2 * ox + 0.5), _snap(2 * oy + 0.5)
            elif move == "zoom_out" and z > lo:
                z, ox, oy = (z - 1, _snap((ox + 0.5) / 2 - 0.5),
                             _snap((oy + 0.5) / 2 - 0.5))
            else:  # a pan of half a viewport
                dx, dy = ((0.5, 0.0), (-0.5, 0.0), (0.0, 0.5), (0.0, -0.5))[d]
                ox, oy = _snap(ox + dx), _snap(oy + dy)
            if due < seconds:
                out.append(Request(due, sess, z, ox, oy))
    out.sort(key=lambda r: (r.due, r.session))
    before = sum(r.due < 0 for r in out)
    return ViewportPlan(tuple(out), min(before,
                                        int(traffic["warmup_requests"])))


def viewport_window(config: dict, req: Request) -> Window:
    """The plane window of one viewport request."""
    re0, im0, re1, im1 = (float(x) for x in config["window"])
    tw = (re1 - re0) / 2 ** req.z
    th = (im1 - im0) / 2 ** req.z
    x, y = re0 + req.ox * tw, im0 + req.oy * th
    return (x, y, x + tw, y + th)


def viewport_tiles(req: Request) -> Tuple[Tuple[int, int, int], ...]:
    """The 2 x 2 tiles ``(z, iy, ix)`` a viewport overlaps, row-major."""
    ix, iy = math.floor(req.ox), math.floor(req.oy)
    return tuple((req.z, y, x) for y in (iy, iy + 1) for x in (ix, ix + 1))


def tile_window(config: dict, tile: Tuple[int, int, int]) -> Window:
    """Plane window of tile ``(z, iy, ix)``: the tile pyramid's window
    cut into 2**z x 2**z tiles (the slippy-map convention)."""
    z, iy, ix = tile
    re0, im0, re1, im1 = (float(x) for x in config["window"])
    tw = (re1 - re0) / float(1 << z)
    th = (im1 - im0) / float(1 << z)
    return (re0 + ix * tw, im0 + iy * th, re0 + (ix + 1) * tw,
            im0 + (iy + 1) * th)
