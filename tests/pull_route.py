"""ops/pull's sparse dispatch, forced for a block of a test."""

import contextlib

import jax

import dst_libp2p_test_node_tpu.ops.pull as pull


@contextlib.contextmanager
def forced(min_dense_bytes, rows=None):
    """`_SPARSE_MIN_DENSE_BYTES` (0: every shape takes the sparse route, the
    one a 100,000-peer scan takes; huge: none does) and, if given,
    `_SPARSE_ROWS` for the block. The jitted scan and step keep their traces
    by argument, not by these, so every cache is dropped on the way in and
    on the way out (as test_pull's test_fallback_path_identical forces the
    fallback, one level up)."""
    saved = pull._SPARSE_MIN_DENSE_BYTES, pull._SPARSE_ROWS
    pull._SPARSE_MIN_DENSE_BYTES = min_dense_bytes
    if rows is not None:
        pull._SPARSE_ROWS = rows
    jax.clear_caches()
    try:
        yield
    finally:
        pull._SPARSE_MIN_DENSE_BYTES, pull._SPARSE_ROWS = saved
        jax.clear_caches()
