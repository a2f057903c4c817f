"""Pallas fused scoring-update kernel.

The heartbeat scan defers the per-round counter decay into two carried
scalars and materializes it once post-scan (ops/heartbeat._apply_decay on
`fmd` and `slow_penalty`), after which every consumer immediately re-reads
the decayed counters through SimState.score — a second full (N, C) HBM
round-trip for a few flops. This kernel fuses the two: one pass over the
row blocks applies both decays (with the flush-to-zero floor) AND emits the
weighted score, so the counters stream through VMEM exactly once.

Routing is static: `score_update_best` takes the kernel on a TPU backend
and the plain-XLA reference (`score_update_xla`, bit-for-bit the
heartbeat/_apply_decay + SimState.score composition) elsewhere. There is no
compile-and-see probe and no environment switch: a kernel that is routed
and does not build raises what Pallas/Mosaic raises. That the kernel
compiles for a v5e at the 100k-peer shape is checked without a chip in
tests/test_chip_compile.py; no user-facing path calls `score_update_best`
yet (runtime/microbench.py and the audit registry time and trace it).

CPU correctness of the kernel body itself is tested with `interpret=True`
(tests/test_score_kernel.py), which runs the Pallas program without Mosaic.
The row-block size consults the microbench autotuner's tuned.json
(native/tuned.py) before the largest-dividing-power-of-two heuristic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .tuned import tuned_block_rows

# three f32 (block, C) tiles live per grid step (two counters in, score
# out, counters updated in place of their input tiles); 512 rows x 64
# slots x 5 arrays = 640 KB — a small fraction of a core's ~16 MB VMEM
_MAX_BLOCK = 512


def _block_rows(n_rows: int) -> int:
    """Tuned row block when tuned.json has a valid entry, else the largest
    power-of-two <= _MAX_BLOCK dividing n_rows (grid steps must tile the
    array exactly)."""
    tuned = tuned_block_rows("score_update", n_rows, _MAX_BLOCK)
    if tuned is not None:
        return tuned
    b = 1
    while b < _MAX_BLOCK and n_rows % (b * 2) == 0:
        b *= 2
    return b


@functools.cache
def _compiled(n_rows: int, cap: int, fmd_weight: float, slow_weight: float,
              fmd_cap: float, decay_to_zero: float, interpret: bool,
              block_rows: int | None = None):
    """Build the pallas_call for one (rows, cap) shape + weight constants.
    Raises whatever Pallas/Mosaic raises.
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

    def kernel(sc_ref, fmd_ref, slow_ref, fmd_out, slow_out, score_out):
        # the (2,) decay-scale vector is VMEM-resident for every grid step
        sc = sc_ref[...]
        f = fmd_ref[...] * sc[0]
        s = slow_ref[...] * sc[1]
        f = jnp.where(f < decay_to_zero, 0.0, f)
        s = jnp.where(s < decay_to_zero, 0.0, s)
        fmd_out[...] = f
        slow_out[...] = s
        score_out[...] = (fmd_weight * jnp.minimum(f, fmd_cap)
                          + slow_weight * s)

    row_spec = pl.BlockSpec((block, cap), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid=(n_rows // block,),
        in_specs=[
            pl.BlockSpec((2,), lambda i: (0,), memory_space=pltpu.VMEM),
            row_spec,
            row_spec,
        ],
        out_specs=[row_spec, row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((n_rows, cap), jnp.float32)] * 3,
        interpret=interpret,
    )


def score_update(fmd, slow_penalty, f_scale, s_scale, params, *,
                 interpret: bool = False, block_rows: int | None = None):
    """(decayed fmd, decayed slow_penalty, score) in one fused pass.

    `f_scale`/`s_scale` are the heartbeat scan's carried decay scalars
    (traced); the weight/cap/flush constants come from `params` and bake
    into the compiled kernel like every other SimParams static.
    `block_rows` is the microbench sweep's explicit row-block override;
    production callers leave it None (tuned.json/heuristic)."""
    scales = jnp.stack([jnp.asarray(f_scale, jnp.float32),
                        jnp.asarray(s_scale, jnp.float32)])
    return _compiled(
        fmd.shape[0], fmd.shape[1], float(params.fmd_weight),
        float(params.slow_weight), float(params.fmd_cap),
        float(params.decay_to_zero), interpret, block_rows,
    )(scales, fmd.astype(jnp.float32), slow_penalty.astype(jnp.float32))


def score_kernel_routed() -> bool:
    """The whole routing rule: the kernel exists to exploit TPU VMEM, so it
    is taken on a TPU backend and nowhere else (interpret mode on CPU is a
    test vehicle, not a win)."""
    return jax.default_backend() == "tpu"


def score_update_best(fmd, slow_penalty, f_scale, s_scale, params):
    """The dispatch point for consumers: the Pallas kernel where
    `score_kernel_routed()`, the plain-XLA formulation elsewhere. A routed
    kernel that fails to build is an error, never a silent XLA run."""
    if score_kernel_routed():
        return score_update(fmd, slow_penalty, f_scale, s_scale, params)
    return score_update_xla(fmd, slow_penalty, f_scale, s_scale, params)


def score_update_xla(fmd, slow_penalty, f_scale, s_scale, params):
    """The plain-XLA reference and the off-TPU formulation: literally the
    ops/heartbeat._apply_decay composition followed by SimState.score, so
    the kernel's correctness target IS the production formula."""
    f = fmd * f_scale
    s = slow_penalty * s_scale
    f = jnp.where(f < params.decay_to_zero, 0.0, f)
    s = jnp.where(s < params.decay_to_zero, 0.0, s)
    score = (params.fmd_weight * jnp.minimum(f, params.fmd_cap)
             + params.slow_weight * s)
    return f, s, score
