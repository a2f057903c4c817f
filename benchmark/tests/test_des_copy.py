"""benchmark/reference/des.py is the frozen yardstick: a copy of the
repository's own DES (tests/test_des_crosscheck.py), kept under benchmark/
so that no later PR can change what `correct` is held to. The tests' DES may
move on; this file holds the copy to today's, bit for bit, on every one of
its CASES, and shows the bfloat16 control failing there. The reference's own
link tables are held to the program's."""

import os
import sys

import numpy as np
import pytest

from benchmark.entries import run as run_entry
from benchmark.reference import des

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def crosscheck():
    sys.path.insert(0, os.path.join(CHECKOUT, "tests"))
    try:
        import test_des_crosscheck
    finally:
        sys.path.pop(0)
    return test_des_crosscheck


def test_every_case_is_covered(crosscheck):
    assert len(crosscheck.CASES) == 20


@pytest.mark.parametrize("case_index", range(20))
def test_copy_equals_the_tests_des(crosscheck, case_index):
    import jax.numpy as jnp

    T = crosscheck
    n, ct, seed, stages, frags, loss, flood, gossip_only = T.CASES[case_index]
    g, params, state, a, (stage, lat, bw) = T._setup(
        n, ct, seed, stages, flood_publish=flood)
    if gossip_only:
        state = state.replace(mesh_mask=jnp.zeros_like(state.mesh_mask))
    loss_stage = (jnp.full((stages + 1, stages + 1), loss, jnp.float32)
                  if loss > 0 else None)
    pub = seed % n
    t0 = float(state.t_ms)
    res, _, plan = T.disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=pub, t0_ms=t0,
        params=params, payload_bytes=15000, fragments=frags,
        with_gossip=True, loss_stage=loss_stage, loss_mode="message",
        return_plan=True)
    plan = {k: None if v is None else np.asarray(v) for k, v in plan.items()}
    conns, rev = np.asarray(a["conns"]), np.asarray(a["rev"])
    want_d, want_r = T.des_delays(conns, rev, plan, params, pub, t0, frags)
    link = des.link_model({k: getattr(params, k)
                           for k in des.LINK_MODEL_KEYS})
    got_d, got_r = des.des_delays(conns, rev, plan, link, pub, t0, frags,
                                  15000)
    np.testing.assert_array_equal(got_r, want_r)
    np.testing.assert_array_equal(got_d, want_d)
    # one precision lower, the same reference is far outside the tolerance
    low_d, low_r = des.des_delays(conns, rev, plan, link, pub, t0, frags,
                                  15000, quantize=des.bfloat16_round)
    c = run_entry.compare(
        low_d, low_r, want_d, want_r,
        {"atol_ms": 0.5, "rtol": 1e-4, "hop_ms": 40}, 0, t0)
    assert c.share_beyond > 0.5


def test_bfloat16_round():
    assert des.bfloat16_round(1.0) == 1.0
    assert des.bfloat16_round(8000.0) == 8000.0
    assert des.bfloat16_round(8001.0) == 8000.0       # 32 ms steps at 8e3
    assert des.bfloat16_round(float("inf")) == float("inf")
    a = np.array([1.0, 8001.0, 257.0, np.inf])
    np.testing.assert_array_equal(
        des.bfloat16_round(a), [1.0, 8000.0, 256.0, np.inf])


def test_reference_link_tables_are_the_programs():
    """benchmark/reference/link_tables.py works the tables out from the run's
    positionals by the source's rule; they are the plan's own to float32."""
    from benchmark.harness import manifest
    from benchmark.harness.experiment import run_experiment
    from benchmark.reference import link_tables

    cell = manifest.load_cell("tiny.headline", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCHMARK.test.json"))
    work = os.path.join(manifest.CHECKOUT, ".bench_work", "test.tables")
    with run_entry.capture_publishes([0, 1]) as taken:
        assert run_experiment(cell, 21, work).ok
    pub, later = taken
    # `idle_links_at_publish`: what the last message left has drained
    assert cell.config["reference"]["idle_links_at_publish"]
    assert 0 < later["plan"]["uplink"].max() < later["t0_ms"]
    assert 0 < later["plan"]["rx_free"].max() < later["t0_ms"]
    own = link_tables.edge_tables(
        pub["conns"], run_entry.arguments(cell)["positionals"],
                                  pub["payload_bytes"], pub["fragments"])
    for key, table in own.items():
        np.testing.assert_allclose(table, pub["plan"][key], rtol=1e-6,
                                   err_msg=key)
    assert (own["lat_edge"][pub["conns"] >= 0] >= 40).all()
