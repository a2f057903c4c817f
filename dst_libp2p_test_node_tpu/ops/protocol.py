"""Protocol-generic step registry: the pub/sub arena's dispatch table.

The repo grew up simulating exactly one protocol — GossipSub v1.1 — and
its runners (ops/heartbeat.py, ops/adversary.py, ops/faults.py,
ops/disseminate.py) are the model of record, bit-pinned by the test canon
and conformance-gated against the numpy spec. A second protocol backend
(ops/episub.py) must face the SAME attacker on the SAME epoch graphs
without perturbing any of that, so the registry follows the house
delegation invariant taken to its logical end:

  the GossipSub ProtocolSpec's fields ARE the existing runner function
  objects — not wrappers, not re-exports through a shim, the very same
  Python callables. Dispatching `get_protocol("gossipsub").run_heartbeats`
  hits the same jit cache entry as calling ops.heartbeat.run_heartbeats
  directly, with zero retraces and bit-identical outputs, because it IS
  that call (tests/test_protocol_registry.py pins the `is` identity and
  the retrace count).

A ProtocolSpec mirrors the EntrypointContract pattern
(analysis/registry.py): a frozen declarative descriptor, with the
behavior living in the ops modules it points at. Per-protocol carry
(episub's tree controller) follows the AdaptiveCtrl discipline — a
separate pytree threaded only through the armed scans, never a SimState
leaf — so `init_ctrl=None` (GossipSub) means the runners keep their
pre-registry signatures exactly.

This module must stay free of the repo's jit idiom: it is a dispatch
table, not an entrypoint, and tests/test_registry_drift.py asserts the
GA-J/GA-S auditors need never see it.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

from .adversary import run_adaptive_heartbeats, run_attacked_heartbeats
from .faults import run_faulted_heartbeats
from .heartbeat import run_heartbeats


@dataclass(frozen=True)
class ProtocolSpec:
    """Frozen descriptor of one pub/sub protocol backend.

    Runner fields hold the module-level entrypoints with the house
    signatures (the run_heartbeats / run_attacked_heartbeats /
    run_adaptive_heartbeats / run_faulted_heartbeats argument contracts);
    protocols with extra carry (episub) prepend their ctrl pytree per the
    AdaptiveCtrl convention and set `init_ctrl`/`protocol_params`.

    `observables` names the per-round obs channels the attacked/adaptive
    runners emit BEYOND the shared attack_observables set — the campaign
    surfaces them per protocol in the arena artifact. `repair_hook` and
    `gossip_emission` name (for docs/auditors) how the backend realizes
    message repair and lazy gossip; the mechanics live in the runners.
    """

    name: str
    run_heartbeats: Callable
    run_attacked_heartbeats: Callable
    run_adaptive_heartbeats: Callable
    run_faulted_heartbeats: Callable
    # fresh per-protocol controller carry for one trial window, or None
    # when the protocol carries everything in SimState (GossipSub)
    init_ctrl: Callable | None = None
    # fresh static per-protocol params (frozen dataclass -> jit static),
    # or None when SimParams alone configures the backend
    protocol_params: Callable | None = None
    repair_hook: str = ""
    gossip_emission: str = ""
    observables: tuple[str, ...] = field(default=())

    def validate(self) -> None:
        if not self.name:
            raise ValueError("ProtocolSpec needs a name")
        for f in ("run_heartbeats", "run_attacked_heartbeats",
                  "run_adaptive_heartbeats", "run_faulted_heartbeats"):
            if not callable(getattr(self, f)):
                raise ValueError(f"ProtocolSpec.{f} must be callable")


_PROTOCOLS: dict[str, ProtocolSpec] = {}


def register_protocol(spec: ProtocolSpec) -> ProtocolSpec:
    spec.validate()
    if spec.name in _PROTOCOLS:
        raise ValueError(f"protocol {spec.name!r} already registered")
    _PROTOCOLS[spec.name] = spec
    return spec


def get_protocol(name: str) -> ProtocolSpec:
    _ensure_builtin()
    try:
        return _PROTOCOLS[name]
    except KeyError:
        raise KeyError(
            f"unknown protocol {name!r}; registered: "
            f"{sorted(_PROTOCOLS)}") from None


def protocol_names() -> list[str]:
    _ensure_builtin()
    return sorted(_PROTOCOLS)


# -- builtin specs -----------------------------------------------------------
#
# GossipSub: the model of record. Every field is the existing module-level
# runner OBJECT — the registry adds a name, not a wrapper, so registry
# dispatch is the pre-registry call (same jit cache entry, zero retraces,
# bit-identical; the acceptance gate of the arena refactor).
#
# Episub is registered lazily to keep this module import-light and to
# avoid a circular import (episub reuses the adversary/fault machinery).

_BUILTIN_DONE = False


def _ensure_builtin() -> None:
    global _BUILTIN_DONE
    if _BUILTIN_DONE:
        return
    _BUILTIN_DONE = True
    register_protocol(ProtocolSpec(
        name="gossipsub",
        run_heartbeats=run_heartbeats,
        run_attacked_heartbeats=run_attacked_heartbeats,
        run_adaptive_heartbeats=run_adaptive_heartbeats,
        run_faulted_heartbeats=run_faulted_heartbeats,
        init_ctrl=None,
        protocol_params=None,
        repair_hook="IHAVE/IWANT gossip + mesh repair (ops/repair.py)",
        gossip_emission="gossip_factor sample of non-mesh peers, "
                        "d_lazy floor (ops/disseminate.py)",
        observables=(),
    ))

    from .episub import (EpisubParams, init_episub_ctrl,
                         run_episub_adaptive_heartbeats,
                         run_episub_attacked_heartbeats,
                         run_episub_faulted_heartbeats,
                         run_episub_heartbeats)

    register_protocol(ProtocolSpec(
        name="episub",
        run_heartbeats=run_episub_heartbeats,
        run_attacked_heartbeats=run_episub_attacked_heartbeats,
        run_adaptive_heartbeats=run_episub_adaptive_heartbeats,
        run_faulted_heartbeats=run_episub_faulted_heartbeats,
        init_ctrl=init_episub_ctrl,
        protocol_params=EpisubParams,
        repair_hook="lazy IHAVE along non-tree edges + re-parenting "
                    "(ops/episub.py)",
        gossip_emission="d_lazy lowest-slot non-tree edges per round",
        observables=("tree_reach_frac", "tree_depth_mean"),
    ))
