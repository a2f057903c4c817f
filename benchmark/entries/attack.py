"""The entry `attack`: the v1.1 attack campaign, `cli.main(["attack",
"--scenario", sc, "-n", N, "--fractions", "0,0.1,0.2", "--seeds",
"s,s+1,s+2,s+3", "--seed", s, <the schedule>, "--json",
<out_dir>/campaign1.json, "--stats-json", <out_dir>/stats1.json])`; no
environment. The rule for a traffic mix: the mix gives the scenario, the
attacker fractions (0 first: the benign baselines the others are measured
against) and the count of trial seeds (`s ... s+k-1`, `s` the experiment's
`--seed`); the configuration gives the network and the schedule.

An experiment is one campaign: ONE network, a benign trial a seed, then the
trials of each fraction as one vmapped attack window and a censored publish
schedule each. `correct`:

  part 1   from stats1.json and campaign1.json (`invariants`): the trials
           the mix states, none quarantined, no retry; a fraction-0 row is
           the benign experiment (no attacker, inflation 1.0, everybody
           covered); every attacked trial keeps `honest_coverage_min` and
           its graylist engages within `hb_budget` + `hb_slack` heartbeats;
           an attacked row's baseline is its seed's fraction-0 row; the
           publishes counted;
  part 2   the digest is the sha256 of campaign1.json without its clock
           fields (`wall_s`, `trials_per_s`, every trial's `wall_s`);
  part 3   the campaign once more with, for ONE attacked trial drawn from
           the seed (`every=True`: all of them), the state before its
           window, its cohort, and the state after every heartbeat, taken
           by walking the window a heartbeat a call beside the timed scan
           (the walk must end where the scan ended, leaf for leaf), its
           publishes with their plans and the state each started from.
           Against benchmark/reference/attack_plain.py: the window heartbeat
           by heartbeat (items 1-20: `exact_differing`, the entries of the
           bool and int leaves and of the carried key that differ, limit 0;
           `float_beyond`, those of the float leaves beyond `window_rtol` /
           `window_atol`, limit 0: the program's analysis/conformance.py has
           the same discipline and the same reason: the host
           performs the engine's float32 operations in the engine's order,
           the tolerance is room for a fused multiply-add on another
           backend), every other leaf untouched; the publishes (items
           101-103): the reference carries its OWN state from the end of
           its walk through the schedule (attack_plain.carried: the honest
           heartbeat between two publishes, what a publish writes, the
           censorship penalty after it), and the state each publish starts
           from is held to that (`start_exact_differing`,
           `start_float_beyond`, limit 0), the plan's delivery mask to the
           mask from the reference's scores and the cohort, exactly, the
           delays to benchmark/reference/des.py through that mask (limits
           `eps` and `eps_hop`), what the publish wrote to the rule's shape
           (`credit_rows_differing`: WHERE a first-delivery credit went and
           how many marks a slow queue earned are the timing model's, data
           to the carry as the plan's draws are to the DES; a credit a
           receiver, none for the publisher, is the rule), and the counters
           after `censorship_penalty_update` to the plain rule on the
           reference's state and the DES's receivers (`penalty_differing`,
           limit 0); and the trial's row (item 200): every number
           of stats1.json's row recomputed from the arrays, the curves from
           the reference's own walk.

A trial beyond the first of `every=True` numbers its items from 1000 x its
index. The control (benchmark/control.py) is attack_plain's own: the
reference computed one precision lower (bfloat16) and put in the program's
place; it has to differ in every item.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import random

import numpy as np

from benchmark.entries import run as run_entry
from benchmark.harness.experiment import Outcome, run_experiment
from benchmark.reference import attack_plain, des

PUBLISH_ITEM = 100      # a trial's publish i is item 100 + i
ROW_ITEM = 200
TRIAL_STRIDE = 1000     # every=True: trial r's items start at 1000 * r
CLOCK_KEYS = ("wall_s", "trials_per_s")


# ---------------------------------------------------------- the invocation


def settings(cell) -> dict:
    """The configuration's `attack` (network and schedule) with what the
    traffic mix gives: scenario, fractions, count of trial seeds."""
    mix = cell.traffic
    fractions = [float(f) for f in mix["fractions"]]
    if fractions[0] != 0.0 or any(f <= 0.0 for f in fractions[1:]):
        raise SystemExit("benchmark: the entry `attack` wants fraction 0 "
                         f"first and only there, got {fractions}")
    return {**cell.config["attack"], "scenario": mix["scenario"],
            "fractions": fractions, "trial_seeds": int(mix["trial_seeds"])}


def trial_seeds(cell, seed: int) -> list[int]:
    return [seed + i for i in range(settings(cell)["trial_seeds"])]


def invocation(cell, seed: int, out_dir: str) -> tuple[list[str], dict]:
    at = settings(cell)
    return ["attack", "--scenario", at["scenario"],
            "-n", str(at["peers"]),
            "--fractions", ",".join(f"{f:g}" for f in at["fractions"]),
            "--seeds", ",".join(str(s) for s in trial_seeds(cell, seed)),
            "--seed", str(seed),
            "--messages", str(at["messages"]),
            "--msg-size", str(at["msg_size"]),
            "--delay-s", str(at["delay_s"]),
            "--warmup-s", str(at["warmup_s"]),
            "--attack-heartbeats", str(at["attack_heartbeats"]),
            "--connect-to", str(at["connect_to"]),
            "--publisher-id", str(at["publisher_id"]),
            "--json", os.path.join(out_dir, "campaign1.json"),
            "--stats-json", os.path.join(out_dir, "stats1.json")], {}


# ------------------------------------------------- part 1 and the digest


def less_clock(campaign: dict) -> dict:
    """The campaign's JSON with its clock fields taken out."""
    kept = {k: v for k, v in campaign.items() if k not in CLOCK_KEYS}
    kept["trials"] = [{k: v for k, v in t.items() if k not in CLOCK_KEYS}
                      for t in campaign.get("trials", [])]
    return kept


def campaign_digest(campaign: dict) -> str:
    return hashlib.sha256(json.dumps(
        less_clock(campaign), sort_keys=True).encode()).hexdigest()


def invariants(cell, out_dir: str) -> dict:
    at, guarantees = settings(cell), cell.config["guarantees"]
    try:
        with open(os.path.join(out_dir, "stats1.json")) as f:
            stats = json.load(f)
        with open(os.path.join(out_dir, "campaign1.json")) as f:
            campaign = json.load(f)
    except (OSError, ValueError) as e:
        return {"faults": [f"artifact missing or not JSON: {e}"]}
    fractions, seeds = at["fractions"], at["trial_seeds"]
    attack = stats.get("attack", {})
    trials = campaign.get("trials", [])
    faults = []
    if [t.get("fraction") for t in trials] != [
            f for f in fractions for _ in range(seeds)]:
        faults.append(f"trials at fractions "
                      f"{[t.get('fraction') for t in trials]}, the mix "
                      f"states {seeds} at each of {fractions}")
    if (campaign.get("degraded") or campaign.get("retries_total")
            or campaign.get("quarantined_trials")):
        faults.append(
            f"degraded {campaign.get('degraded')}, retries "
            f"{campaign.get('retries_total')}, quarantined "
            f"{campaign.get('quarantined_trials')}")
    publishes = len(fractions) * seeds * int(at["messages"])
    counted = {"trials": len(fractions) * seeds,
               "attacked_trials": (len(fractions) - 1) * seeds,
               "vmapped_windows": (len(fractions) - 1) * (seeds > 1),
               "window_heartbeats": (len(fractions) - 1)
               * int(at["attack_heartbeats"]),
               "publishes": publishes}
    for name, value in counted.items():
        if attack.get(name) != value:
            faults.append(f"{name} {attack.get(name)}, the configuration "
                          f"and the mix state {value}")
    baseline = {}
    floor = float(guarantees["honest_coverage_min"])
    for t in trials:
        where = f"trial (fraction {t.get('fraction')}, seed {t.get('seed')})"
        if t.get("fraction") == 0.0:
            baseline[t.get("seed")] = t.get("latency_p50_ms")
            if (t.get("attackers") != 0 or t.get("latency_inflation") != 1.0
                    or t.get("honest_coverage")
                    != guarantees["benign_coverage"]):
                faults.append(
                    f"{where} is not the benign experiment: attackers "
                    f"{t.get('attackers')}, inflation "
                    f"{t.get('latency_inflation')}, coverage "
                    f"{t.get('honest_coverage')}")
            continue
        budget = t.get("hb_budget")
        latest = None if budget is None else budget + guarantees["hb_slack"]
        cover, engaged = t.get("honest_coverage"), t.get("hb_to_graylist")
        if not isinstance(cover, float) or not floor <= cover <= 1.0:
            faults.append(f"{where}: honest coverage {cover}, guaranteed "
                          f"at least {floor}")
        if latest is None or not 1 <= engaged <= latest:
            faults.append(f"{where}: the graylist engaged at heartbeat "
                          f"{engaged}, guaranteed by {latest}")
        if t.get("benign_p50_ms") != baseline.get(t.get("seed")):
            faults.append(f"{where}: baseline p50 {t.get('benign_p50_ms')}, "
                          "its seed's fraction-0 row has "
                          f"{baseline.get(t.get('seed'))}")
    return {"faults": faults, "digest": campaign_digest(campaign),
            "digest_of": "campaign1.json less its clock fields",
            "stats": stats}


def digest_line(outcome: Outcome) -> dict:
    attack = outcome.stats.get("attack", {})
    return {"campaign_sha256": outcome.digest,
            **{k: attack.get(k) for k in (
                "honest_coverage_min", "latency_inflation_max",
                "hb_to_graylist_max", "hb_budget",
                "graylisted_frac_final_min", "attacker_mesh_share_peak",
                "attacker_score_final_mean", "device_reads")}}


# ------------------------------------------------------------------ part 3


def drawn(cell, seed: int) -> int:
    """The attacked trial run.py replays, of (fractions - 1) x seeds in the
    campaign's order."""
    at = settings(cell)
    return random.Random(seed).randrange(
        (len(at["fractions"]) - 1) * at["trial_seeds"])


def host_state(state) -> dict:
    """Every leaf a state holds, as numpy."""
    return {f.name: np.asarray(getattr(state, f.name))
            for f in dataclasses.fields(state)
            if getattr(state, f.name) is not None}


@contextlib.contextmanager
def capture_campaign(walked, planned):
    """Wrap the campaign's attack window and `Simulator`'s `disseminate` for
    the length of a campaign. Yields (windows, publishes): every window
    call's trials, each with its cohort and, where `walked(trial)` (trials
    count from 0 in the campaign's order), the state before the window and
    after every heartbeat, from a walk of one heartbeat a call whose end is
    held to the scan's; every publish with its delays and, where
    `planned(publish)` (publishes count from 0), its plan, the state it
    started from, the state `disseminate` left and the counters as
    `censorship_penalty_update` left them after it."""
    from dst_libp2p_test_node_tpu.ops import adversary
    from dst_libp2p_test_node_tpu.runtime import campaign
    from dst_libp2p_test_node_tpu.runtime import simulator as simmod

    attack_windows, disseminate = campaign._attack_windows, simmod.disseminate
    penalty_update = campaign.censorship_penalty_update
    windows: list[dict] = []
    publishes: list[dict] = []

    def window(sim, attackers, states, adv, steps, **kw):
        outs, obs, ctrls = attack_windows(sim, attackers, states, adv, steps,
                                          **kw)
        a = sim.arrays
        for j, (cohort, start, end) in enumerate(zip(attackers, states,
                                                     outs)):
            trial = {"trial": len(windows), "steps": steps,
                     "attacker": np.asarray(cohort), "fault": None}
            if walked(trial["trial"]):
                st, walk = start, []
                for _ in range(steps):
                    st, _ = adversary.run_attacked_heartbeats(
                        st, a["conns"], a["rev"], a["out_mask"], cohort,
                        sim.params, adv, 1)
                    walk.append(host_state(st))
                scanned = host_state(end)
                off = [k for k, v in walk[-1].items()
                       if not np.array_equal(v, scanned[k])]
                if off:
                    trial["fault"] = (
                        f"trial {trial['trial']}: the walk of {steps} "
                        f"heartbeats ends elsewhere than the timed scan in "
                        f"{off}")
                trial.update(
                    start=host_state(start), walk=walk,
                    conns=np.asarray(a["conns"]), rev=np.asarray(a["rev"]),
                    out_mask=np.asarray(a["out_mask"]))
            windows.append(trial)
        return outs, obs, ctrls

    def publish(state, conns, rev, *args, **kw):
        # a plan costs no second program: the one that returns it is the
        # one `Simulator` dispatches
        res, new_state, plan = disseminate(state, conns, rev, *args, **kw,
                                           return_plan=True)
        pub = {"delay_ms": np.asarray(res.delay_ms, np.float64),
               "received": np.asarray(res.received)}
        if planned(len(publishes)):
            pub.update(
                conns=np.asarray(conns), rev=np.asarray(rev),
                start=host_state(state), after=host_state(new_state),
                plan={k: None if v is None else np.asarray(v)
                      for k, v in plan.items()},
                publisher=int(kw["publisher"]), t0_ms=float(kw["t0_ms"]),
                payload_bytes=int(kw["payload_bytes"]),
                fragments=int(kw["fragments"]),
                with_gossip=bool(kw["with_gossip"]))
        publishes.append(pub)
        return res, new_state

    def penalised(state, conns, rev, attacker, received, *args):
        new_state = penalty_update(state, conns, rev, attacker, received,
                                   *args)
        if "plan" in publishes[-1]:
            publishes[-1].update(
                penalty_received=np.asarray(received),
                penalised=np.asarray(new_state.slow_penalty))
        return new_state

    campaign._attack_windows, simmod.disseminate = window, publish
    campaign.censorship_penalty_update = penalised
    try:
        yield windows, publishes
    finally:
        campaign._attack_windows = attack_windows
        simmod.disseminate = disseminate
        campaign.censorship_penalty_update = penalty_update


def captured(cell, seed: int, out_dir: str,
             every: bool = False) -> tuple[Outcome, list[dict]]:
    at = settings(cell)
    seeds, messages = trial_seeds(cell, seed), int(at["messages"])
    attacked = (len(at["fractions"]) - 1) * len(seeds)
    checked = drawn(cell, seed)
    replayed = range(attacked) if every else [checked]

    # the campaign's order: a benign trial a seed, then the attacked trials
    # fraction by fraction, seed by seed, `messages` publishes each
    def first(r):
        return (len(seeds) + r) * messages

    with capture_campaign(
            lambda r: r in replayed,
            lambda i: i // messages - len(seeds) in replayed) as (
                windows, publishes):
        outcome = run_experiment(cell, seed, out_dir)
    total = (attacked + len(seeds)) * messages
    if outcome.ok and (len(windows) != attacked or len(publishes) != total):
        outcome.faults.append(
            f"captured {len(windows)} window trials and {len(publishes)} "
            f"publishes, wanted {attacked} and {total}")
    outcome.faults += [w["fault"] for w in windows if w["fault"]]
    if not outcome.ok:
        return outcome, []
    arrays = [(p["delay_ms"], p["received"]) for p in publishes]
    rows = {(row["fraction"], row["seed"]): row
            for row in outcome.stats["attack"]["rows"]}
    items = []
    for r in replayed:
        trial = windows[r]
        fraction = at["fractions"][1 + r // len(seeds)]
        trial_seed = seeds[r % len(seeds)]
        base = 0 if r == checked else TRIAL_STRIDE * (r + 1)
        common = {"seed": seed, "drawn": r == checked, "trial": r,
                  "fraction": fraction, "trial_seed": trial_seed,
                  "shared": trial}
        items += [{**common, "kind": "heartbeat", "message": base + k,
                   "heartbeat": k} for k in range(1, trial["steps"] + 1)]
        schedule = publishes[first(r):first(r) + messages]
        items += [{**common, "kind": "publish",
                   "message": base + PUBLISH_ITEM + i + 1, "publish": pub,
                   "index": i, "schedule": schedule}
                  for i, pub in enumerate(schedule)]
        own, benign = first(r), (r % len(seeds)) * messages
        items.append({
            **common, "kind": "row", "message": base + ROW_ITEM,
            "row": rows[(fraction, trial_seed)],
            "hb_budget": outcome.stats["attack"]["hb_budget"],
            "publishes": arrays[own:own + messages],
            "baseline": arrays[benign:benign + messages]})
    return outcome, items


# ------------------------------------------------------- the comparisons


def defence(cell) -> dict:
    return cell.config["defence"]


def _walks(cell, shared: dict, control: bool) -> list[dict]:
    """The reference's walk of a trial's window, kept on the trial (the
    twenty heartbeat items and the row read it); the control's beside it."""
    name = "control_walk" if control else "reference_walk"
    if name not in shared:
        shared[name] = attack_plain.window(
            shared["start"], shared["conns"], shared["rev"],
            shared["out_mask"], shared["attacker"], defence(cell),
            shared["steps"],
            quantize=attack_plain.bfloat16 if control else None)
    return shared[name]


def _heartbeat_record(cell, item: dict, control: bool) -> dict:
    ref, shared = cell.config["reference"], item["shared"]
    k = item["heartbeat"]
    want = _walks(cell, shared, False)[k - 1]
    got = (_walks(cell, shared, True) if control else shared["walk"])[k - 1]
    by_leaf = {leaf: int(np.sum(got[leaf] != want[leaf]))
               for leaf in attack_plain.EXACT_LEAVES + ("key",)}
    numbers = {"exact_differing": sum(by_leaf.values())}
    worst = 0.0
    for leaf in attack_plain.FLOAT_LEAVES:
        a, b = np.asarray(got[leaf], np.float64), np.asarray(want[leaf],
                                                             np.float64)
        by_leaf[leaf] = int(np.sum(~np.isclose(
            a, b, rtol=ref["window_rtol"], atol=ref["window_atol"])))
        worst = max(worst, float(np.max(np.abs(a - b))))
    numbers["float_beyond"] = sum(by_leaf[leaf]
                                  for leaf in attack_plain.FLOAT_LEAVES)
    # what an attacked heartbeat has no business with stays what it was
    written = set(attack_plain.EXACT_LEAVES + attack_plain.FLOAT_LEAVES
                  + ("key",))
    numbers["untouched_differing"] = 0 if control else sum(
        int(np.sum(v != shared["start"][name]))
        for name, v in got.items() if name not in written)
    return {"what": "the state after an attacked heartbeat against the "
            "plain transition", "seed": item["seed"],
            "message": item["message"], "trial": item["trial"],
            "fraction": item["fraction"], "trial_seed": item["trial_seed"],
            "heartbeat": k, "mesh_edges": int(want["mesh_mask"].sum()),
            "by_leaf": by_leaf, "float_max_abs_diff": worst,
            "tolerance": f"rtol {ref['window_rtol']}, atol "
            f"{ref['window_atol']} on float leaves; 0 on bool and int",
            **numbers, **{f"limit_{n}": 0 for n in numbers},
            "passed": not any(numbers.values())}


def _des(cell, pub: dict):
    """The float64 event-queue reference's (delays, received) of a captured
    publish, kept on it."""
    if "reference" not in pub:
        pub["reference"] = run_entry.reference_delays(
            pub, cell, links=settings(cell)["links"])
    return pub["reference"]


def _heartbeats_before(cell, i: int) -> int:
    """The honest heartbeats the schedule runs before publish `i` of a
    trial (none before the first: the window has just ended): those due in
    `delay_s`, the remainder carried as the program carries it."""
    at, hb = settings(cell), float(defence(cell)["heartbeat_ms"])

    def due(k):
        return int((at["warmup_s"] * 1000.0 + k * at["delay_s"] * 1000.0)
                   // hb)
    return due(i) - due(i - 1) if i else 0


def _carried(cell, item: dict, control: bool) -> list[dict]:
    """The reference's own state through the trial's publishes
    (attack_plain.carried), from the end of its own walk of the window; kept
    on the trial. What a publish itself writes is data from the captured
    publish; who got the message is the DES's word."""
    shared = item["shared"]
    name = "control_carried" if control else "reference_carried"
    if name not in shared:
        publishes = []
        for i, pub in enumerate(item["schedule"]):
            publishes.append({
                "heartbeats": _heartbeats_before(cell, i),
                "received": _des(cell, pub)[1],
                "writes": attack_plain.publish_writes(pub["start"],
                                                      pub["after"])})
        shared[name] = attack_plain.carried(
            _walks(cell, shared, control)[-1], shared["conns"],
            shared["rev"], shared["out_mask"], shared["attacker"],
            defence(cell), publishes,
            quantize=attack_plain.bfloat16 if control else None)
    return shared[name]


def _publish_record(cell, item: dict, control: bool) -> dict:
    ref, pub = cell.config["reference"], item["publish"]
    shared, i = item["shared"], item["index"]
    attacker = shared["attacker"]
    quantize = attack_plain.bfloat16 if control else attack_plain.exact
    sound = _carried(cell, item, False)[i]
    # the state the publish starts from: the program's (the control's)
    # against the reference's own, carried from the end of its walk
    got = _carried(cell, item, True)[i] if control else {
        "start": pub["start"],
        "penalised": {"slow_penalty": pub["penalised"]}}
    exact = sum(int(np.sum(got["start"][leaf] != sound["start"][leaf]))
                for leaf in attack_plain.EXACT_LEAVES + ("key",))
    beyond = sum(int(np.sum(~np.isclose(
        np.asarray(got["start"][leaf], np.float64),
        np.asarray(sound["start"][leaf], np.float64),
        rtol=ref["window_rtol"], atol=ref["window_atol"])))
        for leaf in attack_plain.FLOAT_LEAVES)
    # the mask from the reference's OWN scores (the control's own)
    mine = got["start"] if control else sound["start"]
    mask = attack_plain.delivery_mask(
        mine["fmd"], mine["slow_penalty"], pub["conns"], pub["rev"],
        attacker, defence(cell), quantize)
    taken = pub["plan"]["survive"]
    differing = (mask.size if taken is None or taken.shape != mask.shape
                 else int(np.sum(np.asarray(taken) != mask)))
    want_d, want_r = _des(cell, pub)
    got_d, got_r = (run_entry.reference_delays(
        pub, cell, des.bfloat16_round, settings(cell)["links"]) if control
        else (pub["delay_ms"], pub["received"]))
    c = run_entry.compare(got_d, got_r, want_d, want_r, ref,
                          item["message"], pub["t0_ms"])
    # what the publish wrote has the rule's shape, and the counters after
    # the censorship penalty are the reference's, entry for entry
    credit = attack_plain.credit_off(
        attack_plain.publish_writes(pub["start"], pub["after"]),
        pub["start"]["fmd"], want_r, pub["publisher"], defence(cell))
    told = int(np.sum(pub["penalty_received"] != want_r))
    penalty = int(np.sum(got["penalised"]["slow_penalty"]
                         != sound["penalised"]["slow_penalty"]))
    numbers = {"start_exact_differing": exact, "start_float_beyond": beyond,
               "survive_differing": differing, "credit_rows_differing": credit,
               "penalty_receivers_differing": told,
               "penalty_differing": penalty}
    # no attacker's copy and no graylisted edge's is a first delivery: the
    # reference gets there through the mask, which is the program's own iff
    # `survive_differing` is 0
    return {"what": "an attacked publish: the state it starts from against "
            "the reference's own, carried from the window's end; its "
            "delivery mask against the cohort and the reference's scores; "
            "its delays against the float64 reference through that mask; "
            "the counters after the censorship penalty against the plain "
            "rule", "seed": item["seed"],
            "trial": item["trial"], "fraction": item["fraction"],
            "trial_seed": item["trial_seed"], **c.line(),
            "delivering_edges": int(mask.sum()),
            "penalised_edges": int(np.sum(
                sound["penalised"]["slow_penalty"]
                != sound["start"]["slow_penalty"])),
            **numbers, **{f"limit_{n}": 0 for n in numbers},
            "tolerance": f"{ref['atol_ms']} ms + {ref['rtol']} * delay",
            "hop_ms": ref["hop_ms"], **run_entry.limits(ref),
            "passed": not any(numbers.values()) and run_entry.passes(c, ref)}


ROW_EXACT = ("attackers", "hb_to_graylist", "mesh_recovery_hb")
ROW_CLOSE = ("honest_coverage", "latency_p50_ms", "latency_p99_ms",
             "benign_p50_ms", "latency_inflation")


def _row_record(cell, item: dict, control: bool) -> dict:
    shared, params = item["shared"], defence(cell)
    quantize = attack_plain.bfloat16 if control else attack_plain.exact

    def arrays(publishes):
        delays = np.stack([d for d, _ in publishes])
        return (quantize(delays) if control else delays,
                np.stack([r for _, r in publishes]))

    everyone = np.zeros_like(shared["attacker"])
    base = attack_plain.trial_row(
        *arrays(item["baseline"]), everyone, 1.0,
        {"graylisted_frac": [0.0], "attacker_mesh_share": [0.0]}, params)
    want = attack_plain.trial_row(
        *arrays(item["publishes"]), shared["attacker"],
        base["latency_p50_ms"],
        attack_plain.curves(_walks(cell, shared, control), shared["conns"],
                            shared["rev"], shared["attacker"], params,
                            quantize), params)
    got = {**item["row"], "hb_budget": item["hb_budget"]}
    off = [k for k in ROW_EXACT + ("hb_budget",) if got.get(k) != want[k]]
    off += [k for k in ROW_CLOSE
            if got.get(k) is None or not abs(got[k] - want[k]) <= 1e-9]
    return {"what": "stats1.json's row of the trial against the same "
            "numbers from the arrays, the curves from the reference's walk",
            "seed": item["seed"], "message": item["message"],
            "trial": item["trial"], "fraction": item["fraction"],
            "trial_seed": item["trial_seed"], "differing": off,
            "hb_to_graylist": want["hb_to_graylist"],
            "mesh_recovery_hb": want["mesh_recovery_hb"],
            "honest_coverage": want["honest_coverage"],
            "latency_inflation": want["latency_inflation"],
            "attacker_mesh_share_peak": want["attacker_mesh_share_peak"],
            "row_numbers_differing": len(off),
            "limit_row_numbers_differing": 0, "passed": not off}


def against_reference(cell, item: dict, control: bool = False) -> dict:
    return {"heartbeat": _heartbeat_record, "publish": _publish_record,
            "row": _row_record}[item["kind"]](cell, item, control)


def summarised(records: list[dict], control: bool = False) -> dict:
    """Of all items a kind: the sound runs' largest, the control's
    smallest. A heartbeat's and a row's number is its count of differing
    entries; a publish has the `run` entry's three beside the mask's."""
    def differing(r):
        return sum(v for k, v in r.items() if isinstance(v, int)
                   and not k.startswith("limit_")
                   and k.endswith(("_differing", "_beyond")))

    name, of = ("control", min) if control else ("sound", max)
    end = "min" if control else "max"
    beats = [r for r in records if "heartbeat" in r]
    pubs = [r for r in records if "share_beyond" in r]
    rows = [r for r in records if "row_numbers_differing" in r]
    return {
        f"{name}_heartbeat_differing_{end}": of(
            (differing(r) for r in beats), default=None),
        f"{name}_row_differing_{end}": of(
            (differing(r) for r in rows), default=None),
        **{f"{name}_{k}_{end}": of((r[k] for r in pubs), default=None)
           for k in ("start_exact_differing", "start_float_beyond",
                     "survive_differing", "credit_rows_differing",
                     "penalty_differing", "reached_differing",
                     "share_beyond", "share_beyond_hop")},
        **({} if control else {"sound_float_max_abs_diff_max": max(
            (r["float_max_abs_diff"] for r in beats), default=None)})}
