"""Pallas TPU kernel for the segmented max-plus Lindley scan.

TARGET: TPU. Each row of ``n`` elements is laid out as ``(n / 128, 128)``
tiles, row-major, so one block is ``(BLOCK_ROWS, 128)`` consecutive
elements — a whole number of ``(8, 128)`` float32 vreg tiles. Grid =
``(batch, n_blocks)``; the block axis is minor-most (sequential), so the
carry — the composed max-plus map of every element seen so far — lives
in VMEM scratch as a lane-broadcast ``(1, 128)`` pair and never
round-trips HBM between blocks.

Elements are the affine max-plus maps of ``repro.core.sim_scan``:
``(u, v): w -> max(w + u, v)`` — a message contributes ``(X_n, 0)``, a
server's first message (segment head) contributes ``(-inf, 0)``, padding
contributes the identity ``(0, -inf)``. Within a block the inclusive scan
runs in three Hillis-Steele sweeps built from ``pltpu.roll`` and iota
masks (lowered by Mosaic to lane/sublane rotates and selects): along
the 128 lanes of every tile row, then down the rows over each row's
lane-broadcast total, then the carry composes in front. Waits are
``W = max(U, V)`` of the inclusive prefix maps.

float32 (DESIGN.md §8). Validated on CPU via ``interpret=True`` against
the numpy segmented backend; ``interpret`` has no default — the caller
decides from the platform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
BLOCK_ROWS = 512          # tile rows per block: 64K elements, 256 KiB f32


def _combine(a, b):
    """Compose max-plus maps: ``a`` (earlier) followed by ``b``."""
    au, av = a
    bu, bv = b
    return au + bu, jnp.maximum(av + bu, bv)


def _shift(u, v, d: int, axis: int, pos):
    """Maps ``d`` places earlier along ``axis``; the identity before the
    start of the tile."""
    keep = pos >= d
    return (jnp.where(keep, pltpu.roll(u, d, axis), 0.0),
            jnp.where(keep, pltpu.roll(v, d, axis), -jnp.inf))


def _scan(u, v, axis: int):
    """Inclusive scan of a tile along ``axis`` (log2(extent) sweeps)."""
    pos = jax.lax.broadcasted_iota(jnp.int32, u.shape, axis)
    d = 1
    while d < u.shape[axis]:
        u, v = _combine(_shift(u, v, d, axis, pos), (u, v))
        d *= 2
    return u, v


def _lindley_kernel(u_ref, v_ref, w_ref, cu_ref, cv_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():                             # identity map
        cu_ref[...] = jnp.zeros(cu_ref.shape, jnp.float32)
        cv_ref[...] = jnp.full(cv_ref.shape, -jnp.inf, jnp.float32)

    lu, lv = _scan(u_ref[...], v_ref[...], axis=1)    # within tile rows
    shape = lu.shape
    # each row's total (lane 127), broadcast over lanes, scanned down rows
    ru, rv = _scan(jnp.broadcast_to(lu[:, LANES - 1:], shape),
                   jnp.broadcast_to(lv[:, LANES - 1:], shape), axis=0)
    # maps before each row = carry . (rows above), then . (row prefix)
    eu, ev = _shift(ru, rv, 1, 0, jax.lax.broadcasted_iota(
        jnp.int32, shape, 0))
    cu, cv = cu_ref[...], cv_ref[...]
    pu, pv = _combine((jnp.broadcast_to(cu, shape),
                       jnp.broadcast_to(cv, shape)), (eu, ev))
    big_u, big_v = _combine((pu, pv), (lu, lv))
    w_ref[...] = jnp.maximum(big_u, big_v)            # W_n with W_0 = 0
    cu_ref[...], cv_ref[...] = _combine((cu, cv), (ru[-1:], rv[-1:]))


@functools.partial(jax.jit, static_argnames=("interpret",))
def lindley_scan(u: jax.Array, v: jax.Array, *, interpret: bool) -> jax.Array:
    """Batched waits for max-plus element rows.

    u, v: (batch, n) map coefficients in sorted (server, arrival) order.
    The batch axis carries whatever the caller stacks — K candidate
    placements, independent hierarchy stages, or both. Rows are padded
    with the identity ``(0, -inf)`` to whole blocks of up to
    ``BLOCK_ROWS`` tile rows. Returns W: (batch, n) float32 waiting
    times.
    """
    u = jnp.asarray(u, jnp.float32)
    v = jnp.asarray(v, jnp.float32)
    b, n = u.shape
    rows = SUBLANES * pl.cdiv(n, SUBLANES * LANES)
    br = min(BLOCK_ROWS, rows)
    rows = br * pl.cdiv(rows, br)
    npad = rows * LANES
    if npad > n:
        u = jnp.pad(u, ((0, 0), (0, npad - n)))
        v = jnp.pad(v, ((0, 0), (0, npad - n)), constant_values=-jnp.inf)
    spec = pl.BlockSpec((None, br, LANES), lambda bi, ci: (bi, ci, 0))
    w = pl.pallas_call(
        _lindley_kernel,
        grid=(b, rows // br),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, LANES), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="lindley_scan",
    )(u.reshape(b, rows, LANES), v.reshape(b, rows, LANES))
    return w.reshape(b, npad)[:, :n]
