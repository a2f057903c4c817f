"""Pallas VMEM-gather kernel for the receiver-side fixpoint. NOT ROUTED:
parallel/exchange._src_gather is the plain XLA gather.

The hot gather of the receiver-side formulation (parallel/exchange._inc_from)
is `t_all[src]`: an (N, C) int32 index into the (N,) f32 arrival-time vector,
once per fixpoint iteration. The whole t vector is tiny (400 KB at 100k
peers, 4 MB at 1M), so the kernel here pins it VMEM-resident for the entire
row sweep and gathers each row block against it with one vectorized take.

The installed toolchain (jax 0.9.0 / libtpu 0.0.34) refuses it when asked
to compile for a v5e: `NotImplementedError: Only 2D gather is supported`
(the 1-D `jnp.take` below), at every shape tried, and under vmap the (n_src,)
block additionally fails the (8, 128) block-shape rule. Mosaic's 2-D gather
takes indices of the operand's own shape along one axis, which is not an
(N, C)-index lookup into an N-vector. So nothing routes here: there is no
capability probe and no environment switch, and a caller that wants the
kernel calls `vmem_gather` itself and gets the compiler's error. What stays
is the kernel body, for the interpret-mode test (tests/test_exact_prefix.py)
and the microbench block sweep, until ROADMAP speed item 3 / design item 5
either gives it a formulation Mosaic lowers or deletes it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .tuned import tuned_block_rows

# largest row-block whose int32 index + f32 output tiles stay a small
# fraction of VMEM next to the resident t vector (8 * C * 8 bytes per
# 8-row step; 512 rows x 64 slots = 256 KB of tiles)
_MAX_BLOCK = 512


def _block_rows(n_rows: int) -> int:
    """The microbench autotuner's tuned.json block when it has a valid
    entry (native/tuned.py), else the largest power-of-two row block
    <= _MAX_BLOCK dividing n_rows (grid steps must tile the array exactly;
    every simulator shape is a round number, and a worst-case odd N just
    runs block=1 under interpret in tests — _compiled rejects it for the
    compiled kernel)."""
    tuned = tuned_block_rows("vmem_gather", n_rows, _MAX_BLOCK)
    if tuned is not None:
        return tuned
    b = 1
    while b < _MAX_BLOCK and n_rows % (b * 2) == 0:
        b *= 2
    return b


@functools.cache
def _compiled(n_rows: int, cap: int, n_src: int, interpret: bool,
              block_rows: int | None = None):
    """Build the pallas_call for one (rows, cap, src-len) shape. Raises
    whatever Pallas/Mosaic raises.
    `block_rows` overrides the tuned/heuristic block (the microbench
    sweep's knob); it must tile n_rows exactly."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block = block_rows if block_rows is not None else _block_rows(n_rows)
    if n_rows % block != 0:
        raise ValueError(f"block_rows {block} does not tile {n_rows} rows")
    if not interpret and block < 8:
        # sub-tile row blocks can't meet the (8, 128) f32 tiling floor
        raise ValueError(f"row count {n_rows} leaves block {block} < 8")

    def kernel(t_ref, idx_ref, out_ref):
        # the whole t vector is VMEM-resident (index_map pins block 0 for
        # every grid step); one vectorized take per row block
        idx = idx_ref[...]
        out_ref[...] = jnp.take(t_ref[...], idx.reshape(-1),
                                axis=0).reshape(idx.shape)

    return pl.pallas_call(
        kernel,
        grid=(n_rows // block,),
        in_specs=[
            pl.BlockSpec((n_src,), lambda i: (0,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block, cap), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block, cap), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_rows, cap), jnp.float32),
        interpret=interpret,
    )


def vmem_gather(t_all: jnp.ndarray, src: jnp.ndarray, *,
                interpret: bool = False,
                block_rows: int | None = None) -> jnp.ndarray:
    """out[q, j] = t_all[max(src[q, j], 0)] via the VMEM-resident kernel.
    Same clip-negative-to-0 convention as exchange._src_gather (pad slots are
    masked by the caller's validity flags, so row 0's value is dead
    there). `block_rows` is the microbench sweep's explicit row-block
    override; production callers leave it None (tuned.json/heuristic)."""
    idx = jnp.clip(src, 0)
    return _compiled(src.shape[0], src.shape[1], t_all.shape[0],
                     interpret, block_rows)(t_all.astype(jnp.float32), idx)
