"""graftkern kernel 2: the Straus MSM window accumulator.

The inner loop that dominates ed25519.msm_window_sums — per-window
16-entry table selection plus the masked binary-tree point-add fold
over the batch — fused into one kernel so a window's selected points,
cached forms and every tree level's intermediate limbs stay in VMEM:
the lax path round-trips each of those through XLA-scheduled buffers
between the gather and every point_add's eight conv launches.

Shape: ONE kernel invocation holds the whole per-point table and loops
the 64 MSB-first nibble windows with an in-kernel ``lax.fori_loop`` —
the loop body (selection + tree) traces once, and the table is read
into VMEM once for all 64 windows instead of once per window (the
grid-per-window form re-fetched it 64x AND unrolled the tree 64x into
the program, which priced the interpreter out of the CPU test lane).
Selection is a ONE-HOT SELECT over the 16 entries (exact, and the
vector-friendly form — no gather unit dependency); identity table
entries make padding and digit-0 rows vanish without a separate mask,
the same trick as the lax path.

Layout: the wrapper hands the kernel entry-major tables whose rows pack
a point's X|Y|Z|T limbs into the 128 lanes (4 x 32: no lane is padding),
so the table is B * 8 KB of VMEM — 8 MB at B = 1024, 16 MB for the
2048-point MSM of a 1024-signature RLC batch, where ``_accum`` raises
the scoped VMEM limit past its 16 MiB default.  Per-shard batches on
the mesh path are far smaller.

Bit-identity: the tree replays ed25519._tree_sum's exact order
(point_add(pts[:m], to_cached(pts[m:])), halving) with the fieldops
transliterations of add_t/to_cached_t, so window sums match the lax
reference limb for limb.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import fieldops as FK
from .backend import interpret_default

_WINDOWS = 64
_TABLE = 16
_SUBLANES = 8
# Rows one in-kernel step touches (see _msm_kernel): the block a rolled
# loop body is unrolled over.  64 rows keeps a point add's conv
# temporaries (64 x 1024 f32 per multiply) at 256 KB.
_BLOCK_ROWS = 64
# VMEM asked for beyond the resident arrays: the block temporaries of
# one point add plus the compiler's own scratch.
_VMEM_HEADROOM = 16 << 20


_COORD_LANES = FK.NLIMBS                  # 4 coordinates x 32 limbs = 128


def _unpack_coord(packed: jnp.ndarray, c: int) -> jnp.ndarray:
    """Coordinate c of packed X|Y|Z|T rows -> one padded limb row
    (lanes 0..31, zeros above — the fieldops layout)."""
    lane = FK.lane_iota(packed.shape)
    shifted = packed if c == 0 else \
        pltpu.roll(packed, FK.NLANES - c * _COORD_LANES, 1)
    return jnp.where(lane < FK.NLIMBS, shifted, 0)


def _msm_kernel(b, tab_ref, dig_ref, o_ref, pts_ref):
    """``b`` (static): the batch the tree folds.  tab_ref (16, rows,
    128): entry-major tables, each row a point packed X|Y|Z|T over the
    128 lanes; dig_ref (rows, 128): digits in lanes 0..63; o_ref (64,
    128): packed window sums; pts_ref (4, rows, 128): the tree's
    working set, one padded limb row per point and coordinate; rows =
    max(b, 8).

    Mosaic unrolls every array op into (8, 128)-tile instructions, so
    nothing here touches more than _BLOCK_ROWS rows at once: selection
    and the wide tree levels walk row blocks with rolled fori_loops
    (compile time and code size then do not grow with B), and only the
    last levels, one block wide, run on values.  No value is sliced at
    a runtime offset; the sub-tile tail rotates sublanes instead of
    slicing below a tile."""
    rows = dig_ref.shape[0]
    blk = min(rows, _BLOCK_ROWS)

    def block(i):
        return pl.ds(pl.multiple_of(i * blk, blk), blk)

    def window(j, carry):
        def select(i, c_):
            # Column j as a (blk, 1) masked lane reduction — Mosaic
            # lowers no dynamic_slice on values, and a runtime lane
            # offset on a ref is unaligned.
            digs = dig_ref[block(i), :]
            dig = jnp.sum(jnp.where(FK.lane_iota(digs.shape) == j, digs, 0),
                          axis=1, keepdims=True)
            packed = jnp.zeros((blk, FK.NLANES), jnp.int32)
            for e in range(_TABLE):  # one-hot select; entry 0 = identity
                packed = jnp.where(dig == e, tab_ref[e, block(i), :], packed)
            for c in range(4):
                pts_ref[c, block(i), :] = _unpack_coord(packed, c)
            return c_

        jax.lax.fori_loop(0, rows // blk, select, 0)
        def level(lvl, c_):
            # rows [0, m) += rows [m, 2m) (_tree_sum order), one block
            # per step: a step writes only the rows it alone reads.
            m = b >> (lvl + 1)

            def step(i, c__):
                upper = pl.ds(pl.multiple_of(m + i * blk, blk), blk)
                out = FK.add_cached(
                    tuple(pts_ref[c, block(i), :] for c in range(4)),
                    FK.to_cached(
                        tuple(pts_ref[c, upper, :] for c in range(4))))
                for c in range(4):
                    pts_ref[c, block(i), :] = out[c]
                return c__

            return jax.lax.fori_loop(0, m // blk, step, c_)

        # The levels wider than a block: ONE rolled body for all of them.
        jax.lax.fori_loop(0, (rows // blk).bit_length() - 1, level, 0)
        m = min(b, blk)
        pts = tuple(pts_ref[c, 0:blk, :] for c in range(4))
        while m > 1:
            m //= 2
            if m >= _SUBLANES:
                first = tuple(c[:m] for c in pts)
                second = tuple(c[m:] for c in pts)
            else:
                # Below one tile: keep all 8 sublanes and bring row
                # r + m to row r by rotation.  Rows >= m then hold
                # sums nobody reads (row ops are independent), so row 0
                # still replays _tree_sum exactly.
                first = pts
                second = tuple(pltpu.roll(c, _SUBLANES - m, 0) for c in pts)
            pts = FK.add_cached(first, FK.to_cached(second))
        out = pts[0]                  # repack X|Y|Z|T (disjoint lanes)
        for c in range(1, 4):
            out = out + pltpu.roll(pts[c], c * _COORD_LANES, 1)
        o_ref[pl.ds(j, 1), :] = out[0:1]
        return carry

    jax.lax.fori_loop(0, _WINDOWS, window, 0)


# jit-wrapped: one pallas trace per (B,) shape (kern package docstring).
@jax.jit
def _accum(table: jnp.ndarray, digits: jnp.ndarray) -> jnp.ndarray:
    b = table.shape[0]
    # A batch below one tile is zero-padded to 8 rows: the tree still
    # starts at m = b, the padding only fills the tile.
    rows = max(b, _SUBLANES)
    tab = jnp.moveaxis(table, 1, 0).reshape(_TABLE, b, FK.NLANES)
    tab = jnp.pad(tab, [(0, 0), (0, rows - b), (0, 0)])
    dig = jnp.pad(digits, [(0, rows - b), (0, FK.NLANES - _WINDOWS)])
    # Resident set: table + digits + working set (rows * 10.5 KB: 21 MB
    # at the 2048-point MSM of a 1024-signature RLC batch) — past the
    # 16 MiB default scoped limit there, far inside a v5e core's
    # 128 MiB of VMEM.
    resident = (_TABLE + 1 + 4) * rows * FK.NLANES * 4
    out = pl.pallas_call(
        functools.partial(_msm_kernel, b),
        out_shape=jax.ShapeDtypeStruct((_WINDOWS, FK.NLANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((4, rows, FK.NLANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=resident + _VMEM_HEADROOM),
        interpret=interpret_default(),
    )(tab, dig)
    return out.reshape(_WINDOWS, 4, FK.NLIMBS)


def msm_window_accum(table: jnp.ndarray,
                     digits: jnp.ndarray) -> jnp.ndarray:
    """Per-window Straus sums from a prebuilt table — the Pallas route
    of the selection + tree half of ed25519.msm_window_sums.

    Args:
      table:  (B, 16, 4, 32) int32 ext tables (ed25519.msm_table; entry
              0 is the identity, so padding/excluded rows select it).
      digits: (B, 64) int32 MSB-first 4-bit windows.  B must be a power
              of two (msm_window_sums pads before calling).
    Returns:
      (64, 4, 32) int32 MSB-first window sums, bit-identical to the lax
      chunked-scan path.
    """
    b = table.shape[0]
    if b < 1 or b & (b - 1):
        raise ValueError(
            f"msm_window_accum batch must be a power of two, got {b}")
    return _accum(jnp.asarray(table, jnp.int32),
                  jnp.asarray(digits, jnp.int32))
