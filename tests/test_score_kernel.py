"""Pallas fused scoring-update kernel + autotuned block table contracts.

native/score_update.py: interpret mode
is the CPU correctness vehicle for the kernel body (counters bitwise
against `score_update_xla` — which IS the heartbeat _apply_decay +
SimState.score composition — and the weighted score to ulp-level FMA
tolerance, the same class of difference XLA's own fusion choices introduce
between jitted and eager evaluations of the reference formula); routing
is a static rule on the backend with no probe and no env switch, a routed
kernel that cannot build raises, and off TPU the `score_update_best`
dispatcher IS the XLA formulation. The block chooser consults the microbench autotuner's
tuned.json (native/tuned.py) before the power-of-two heuristic — a
malformed or non-tiling entry is ignored, never an invalid grid.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dst_libp2p_test_node_tpu.native import score_update as sk
from dst_libp2p_test_node_tpu.native import tuned
from dst_libp2p_test_node_tpu.ops.state import SimParams


def _params(n, c):
    return SimParams(n=n, capacity=c, slow_weight=-10.0)


def _counters(n, c, seed=0):
    rng = np.random.default_rng(seed)
    # span the flush-to-zero cutoff (decay_to_zero default 0.01) so the
    # where() branch is live in both formulations
    fmd = jnp.asarray(rng.uniform(0.0, 3.0, size=(n, c)).astype(np.float32))
    slow = jnp.asarray(
        rng.uniform(0.0, 0.5, size=(n, c)).astype(np.float32))
    return fmd, slow


def _assert_matches_reference(got, want):
    """The probe's contract: carried counters bit-for-bit, the weighted
    score to ulp-level FMA-contraction tolerance."""
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]),
                                  err_msg="fmd")
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]),
                                  err_msg="slow_penalty")
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                               rtol=1e-5, atol=1e-6, err_msg="score")


@pytest.mark.parametrize("shape", [(64, 5), (30, 7), (256, 8)])
def test_interpret_mode_matches_xla(shape):
    n, c = shape
    params = _params(n, c)
    fmd, slow = _counters(n, c)
    want = sk.score_update_xla(fmd, slow, 0.9, 0.8, params)
    got = sk.score_update(fmd, slow, 0.9, 0.8, params, interpret=True)
    _assert_matches_reference(got, want)


def test_block_rows_override_validation():
    params = _params(64, 5)
    fmd, slow = _counters(64, 5)
    # an explicit block that tiles exactly is accepted and bit-equal
    want = sk.score_update_xla(fmd, slow, 0.9, 0.8, params)
    got = sk.score_update(fmd, slow, 0.9, 0.8, params, interpret=True,
                          block_rows=16)
    _assert_matches_reference(got, want)
    # a non-tiling block must refuse (the grid would overrun the array)
    with pytest.raises(ValueError, match="does not tile"):
        sk.score_update(fmd, slow, 0.9, 0.8, params, interpret=True,
                        block_rows=24)
    # compiled (non-interpret) builds reject sub-tile blocks below the
    # (8, 128) f32 floor before ever reaching Mosaic
    with pytest.raises(ValueError, match="< 8"):
        sk._compiled(12, 8, 1.0, -10.0, 100.0, 0.01, False, 4)


def test_routing_is_static_and_env_free(monkeypatch):
    # CI runs CPU: the kernel is not routed here, and the retired
    # DST_PALLAS_SCORE switch changes nothing — the rule is the backend
    assert not hasattr(sk, "score_kernel_available")
    for env in (None, "0", "1"):
        if env is None:
            monkeypatch.delenv("DST_PALLAS_SCORE", raising=False)
        else:
            monkeypatch.setenv("DST_PALLAS_SCORE", env)
        assert sk.score_kernel_routed() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sk.score_kernel_routed() is True


def test_routed_kernel_that_fails_to_build_raises(monkeypatch):
    # a backend that claims to be a TPU routes the kernel; here it cannot
    # build (no Mosaic on the CPU backend), and that must surface as an
    # error — never as a silent run of the XLA formulation
    params = _params(128, 6)
    fmd, slow = _counters(128, 6, seed=2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(Exception) as err:
        jax.block_until_ready(jax.jit(
            lambda f, s: sk.score_update_best(f, s, 0.9, 0.8, params)
        )(fmd, slow))
    assert "interpret" in str(err.value).lower() or "pallas" in str(
        err.value).lower()


def test_dispatcher_is_the_xla_formulation_off_tpu():
    # score_update_best inside a jit is the XLA formulation where the
    # kernel is not routed — same values as calling the reference directly
    params = _params(128, 6)
    fmd, slow = _counters(128, 6, seed=1)
    got = jax.jit(
        lambda f, s: sk.score_update_best(f, s, 0.9, 0.8, params))(fmd, slow)
    # the identical jitted program around the reference: the dispatcher
    # added nothing, so the outputs are the same executable's, bit-for-bit
    want = jax.jit(
        lambda f, s: sk.score_update_xla(f, s, 0.9, 0.8, params))(fmd, slow)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_tuned_table_lookup_and_fallbacks(tmp_path, monkeypatch):
    path = tmp_path / "tuned.json"
    monkeypatch.setenv("DST_TUNED_JSON", str(path))
    try:
        # no file yet: heuristic fallback (largest dividing power of two)
        tuned.invalidate_cache()
        assert tuned.tuned_block_rows("score_update", 64, 512) is None
        assert sk._block_rows(64) == 64
        # a valid entry is honored by the kernel's chooser
        path.write_text(json.dumps({"score_update": {"block_rows": 16}}))
        tuned.invalidate_cache()
        assert tuned.tuned_block_rows("score_update", 64, 512) == 16
        assert sk._block_rows(64) == 16
        # unusable entries fall back rather than produce an invalid grid:
        # non-tiling, bool, float, negative, over the VMEM ceiling, wrong
        # shape — and malformed JSON drops the whole table
        assert tuned.tuned_block_rows("score_update", 50, 512) is None
        for bad in (True, 16.0, -8, 1024, "16", None):
            path.write_text(json.dumps({"score_update": {"block_rows": bad}}))
            tuned.invalidate_cache()
            assert tuned.tuned_block_rows("score_update", 64, 512) is None, bad
        path.write_text(json.dumps({"score_update": [16]}))
        tuned.invalidate_cache()
        assert tuned.tuned_block_rows("score_update", 64, 512) is None
        path.write_text("{not json")
        tuned.invalidate_cache()
        assert tuned.tuned_block_rows("score_update", 64, 512) is None
        assert sk._block_rows(64) == 64
    finally:
        tuned.invalidate_cache()


def test_microbench_sweep_smoke():
    from dst_libp2p_test_node_tpu.runtime import microbench as mb

    # interpret mode admits sub-8 blocks; compiled mode must not
    assert mb._candidate_blocks(96, interpret=False) == [8, 16, 32]
    assert 4 in mb._candidate_blocks(96, interpret=True)
    out = mb.sweep_kernels(n_rows=64, cap=8, reps=1)
    assert out["interpret"] is True  # CPU backend sweeps in interpret mode
    for kernel in ("vmem_gather", "score_update"):
        entry = out["kernels"][kernel]
        assert str(entry["best_block_rows"]) in entry["candidates"]
        assert entry["best_wall_s"] > 0.0
