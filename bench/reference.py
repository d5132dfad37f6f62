"""Plain reference: the exhaustive escape-time render, written out here.

Every pixel is iterated to ``max_dwell`` with no subdivision, no cache
and no batching. It imports nothing of the program under test, so the
yardstick stays put when the program changes.

Semantics (the escape-time convention of the paper's CUDA baseline):
``z0 = c``; while ``dwell < max_dwell`` and ``|z|^2 < 4``: step ``z``
and count one. Interior points carry ``max_dwell``. Pixel ``(x, y)``
maps to ``re0 + x * ((re1 - re0) / n)``, ``im0 + y * ((im1 - im0) /
n)``, with the window given as runtime data in the render's own float
type: a window folded into the program as constants compiles to other
roundings.

``dtype`` is the float type of the whole computation. float32 is what
the configurations state; bfloat16 is the control that the comparison
in ``bench.check`` has to refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["STEPS", "DEFAULT_WINDOWS", "render", "render_many"]


def _mandelbrot(zr, zi, cr, ci):
    return zr * zr - zi * zi + cr, 2.0 * zr * zi + ci


def _julia(zr, zi, cr, ci):  # the dynamic plane of z -> z^2 + c0
    return zr * zr - zi * zi + -0.7269, 2.0 * zr * zi + 0.1889


def _burning_ship(zr, zi, cr, ci):
    return zr * zr - zi * zi + cr, 2.0 * jnp.abs(zr) * jnp.abs(zi) + ci


def _multibrot3(zr, zi, cr, ci):  # z^3 by repeated complex multiply
    wr, wi = zr, zi
    for _ in range(2):
        wr, wi = wr * zr - wi * zi, wr * zi + wi * zr
    return wr + cr, wi + ci


STEPS = {
    "mandelbrot": _mandelbrot,
    "julia": _julia,
    "burning_ship": _burning_ship,
    "multibrot": _multibrot3,
}

# each workload's own default window (re0, im0, re1, im1)
DEFAULT_WINDOWS = {
    "mandelbrot": (-1.5, -1.0, 0.5, 1.0),
    "julia": (-1.6, -1.6, 1.6, 1.6),
    "burning_ship": (-2.5, -2.0, 1.5, 2.0),
    "multibrot": (-1.5, -1.5, 1.5, 1.5),
}


_STATIC = ("n", "max_dwell", "workload", "dtype")


def _render_one(window, *, n, max_dwell, workload, dtype):
    step = STEPS[workload]
    re0, im0, re1, im1 = window[0], window[1], window[2], window[3]
    ys = jax.lax.broadcasted_iota(dtype, (n, n), 0)
    xs = jax.lax.broadcasted_iota(dtype, (n, n), 1)
    cr = re0 + xs * ((re1 - re0) / n)
    ci = im0 + ys * ((im1 - im0) / n)

    def body(_, carry):
        zr, zi, dwell = carry
        live = (zr * zr + zi * zi) < 4.0
        nzr, nzi = step(zr, zi, cr, ci)
        return (jnp.where(live, nzr, zr), jnp.where(live, nzi, zi),
                jnp.where(live, dwell + 1, dwell))

    carry = (cr, ci, jnp.zeros((n, n), jnp.int32))
    return jax.lax.fori_loop(0, max_dwell, body, carry)[2]


_render = jax.jit(_render_one, static_argnames=_STATIC)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _render_block(windows, **static):
    return jax.vmap(functools.partial(_render_one, **static))(windows)


def render(window, *, n: int, max_dwell: int, workload: str = "mandelbrot",
           dtype=jnp.float32) -> jax.Array:
    """Dwell image ``[n, n]`` int32 of one window, rows along imag."""
    if workload not in STEPS:
        raise KeyError(f"the reference has no workload {workload!r}; "
                       f"it knows {sorted(STEPS)}")
    w = jnp.asarray(np.asarray(window, np.float64).astype(np.float32),
                    dtype=dtype)
    return _render(w, n=int(n), max_dwell=int(max_dwell),
                   workload=workload, dtype=jnp.dtype(dtype))


def render_many(windows, *, n: int, max_dwell: int,
                workload: str = "mandelbrot", dtype=jnp.float32,
                block: int = 1):
    """Yield the host image of each window in turn, ``block`` windows to
    a device call (the last block padded with its first window, so one
    program serves every block)."""
    if workload not in STEPS:
        raise KeyError(f"the reference has no workload {workload!r}; "
                       f"it knows {sorted(STEPS)}")
    windows = [np.asarray(w, np.float64).astype(np.float32)
               for w in windows]
    for i in range(0, len(windows), block):
        part = windows[i:i + block]
        padded = part + [part[0]] * (block - len(part))
        out = np.asarray(_render_block(
            jnp.asarray(np.stack(padded), dtype=dtype), n=int(n),
            max_dwell=int(max_dwell), workload=workload,
            dtype=jnp.dtype(dtype)))
        yield from out[:len(part)]
