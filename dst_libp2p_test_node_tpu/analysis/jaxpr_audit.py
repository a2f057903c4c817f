"""Engine 1 — jaxpr auditor: trace the hot entrypoints abstractly and
certify them against their declared contracts.

Everything here is ABSTRACT: ``jax.make_jaxpr`` / ``jax.eval_shape`` /
``jax.jit(...).lower(...)`` trace and lower without touching a device, so
the full audit runs in a few seconds on CPU and is safe in CI.

Codebase-wide rules (applied to every registered entrypoint):

  GA-J001  no pure_callback/io_callback/debug_callback/infeed/outfeed inside
           a scan or while_loop body — a host round-trip per loop iteration
           serializes the fixpoint that the whole design keeps on-device.
  GA-J002  no float64/int64 avals and no weak_type=True avals in loop
           carries. A weak-typed carry (a Python scalar smuggled into the
           carry tuple) re-promotes on every feed-back and is the classic
           silent recompile-churn bug; x64 doubles the state bandwidth.

Contract-driven rules (enabled per entrypoint by its registry entry):

  GA-J003  surviving-``cond`` census >= the declared count (vmapped conds
           lower to ``select_n`` and execute both branches).
  GA-J004  declared donation actually aliases in the lowering text.
  GA-J005  distinct compile keys across the declared ladder match the
           declared count, and feedback outputs' avals match the argument
           avals they are carried back into.
"""

from __future__ import annotations

import inspect
import warnings

from .contracts import EntrypointContract, TraceSpec
from .report import Violation

CALLBACK_PRIMS = {
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "infeed", "outfeed", "host_callback_call",
}
X64_DTYPES = {"float64", "int64", "uint64", "complex128"}

# jaxpr-holding eqn params that mean "this subtree is a loop body"
_LOOP_BODY_PARAMS = {"body_jaxpr"}           # while_loop
_LOOP_COND_PARAMS = {"cond_jaxpr"}           # while_loop predicate
_SCAN_BODY_PARAM = "jaxpr"                   # scan (when primitive is scan)


def _subjaxprs(eqn):
    """Yield (closed_jaxpr, enters_loop_body) for every sub-jaxpr of eqn."""
    from jax.extend.core import ClosedJaxpr

    for key, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else [val]
        for v in vals:
            inner = None
            if isinstance(v, ClosedJaxpr):
                inner = v.jaxpr
            elif hasattr(v, "eqns"):
                inner = v
            if inner is None:
                continue
            is_loop = (
                key in _LOOP_BODY_PARAMS or key in _LOOP_COND_PARAMS
                or (eqn.primitive.name == "scan" and key == _SCAN_BODY_PARAM))
            yield inner, is_loop


def iter_eqns(jaxpr, in_loop: bool = False):
    """Depth-first (eqn, in_loop_body) over a jaxpr and all sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        for sub, enters_loop in _subjaxprs(eqn):
            yield from iter_eqns(sub, in_loop or enters_loop)


def primitive_census(jaxpr) -> dict[str, int]:
    counts: dict[str, int] = {}
    for eqn, _ in iter_eqns(jaxpr):
        name = eqn.primitive.name
        counts[name] = counts.get(name, 0) + 1
    return counts


def _src_anchor(fn) -> tuple[str, int]:
    """(file, line) of the entrypoint's def, unwrapping jit wrappers."""
    import os

    target = inspect.unwrap(fn, stop=lambda f: False)
    for attr in ("__wrapped__", "_fun", "func"):
        inner = getattr(target, attr, None)
        if inner is not None and callable(inner):
            target = inner
    try:
        path = inspect.getsourcefile(target) or "<unknown>"
        _, line = inspect.getsourcelines(target)
        return os.path.relpath(path), line
    except (TypeError, OSError):
        return "<unknown>", 0


def trace_entrypoint(spec: TraceSpec):
    """make_jaxpr through a zero-arg closure — statics ride in captured."""
    import jax

    return jax.make_jaxpr(spec.thunk())()


def _carry_avals(eqn):
    """Loop-carried avals of a scan or while eqn."""
    if eqn.primitive.name == "scan":
        inner = eqn.params["jaxpr"].jaxpr
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        return inner.invars[nc:nc + nk]
    if eqn.primitive.name == "while":
        inner = eqn.params["body_jaxpr"].jaxpr
        nb = eqn.params["body_nconsts"]
        return inner.invars[nb:]
    return []


def _check_loop_rules(closed, name, file, line) -> list[Violation]:
    out = []
    for eqn, in_loop in iter_eqns(closed.jaxpr):
        prim = eqn.primitive.name
        if prim in CALLBACK_PRIMS and in_loop:
            out.append(Violation(
                rule="GA-J001", file=file, line=line, entrypoint=name,
                message=f"{prim} inside a scan/while body — one host "
                        "round-trip per loop iteration"))
        if prim in ("scan", "while"):
            for var in _carry_avals(eqn):
                aval = var.aval
                dt = str(getattr(aval, "dtype", ""))
                weak = bool(getattr(aval, "weak_type", False))
                if dt in X64_DTYPES:
                    out.append(Violation(
                        rule="GA-J002", file=file, line=line, entrypoint=name,
                        message=f"{prim} carry aval {aval} is x64 — double "
                                "state bandwidth in the hot loop"))
                elif weak:
                    out.append(Violation(
                        rule="GA-J002", file=file, line=line, entrypoint=name,
                        message=f"{prim} carry aval {aval} is weak-typed — "
                                "a Python scalar in the carry re-promotes "
                                "every feed-back (recompile churn); wrap it "
                                "in jnp.asarray with an explicit dtype"))
    return out


def _check_cond_survival(closed, contract, file, line) -> list[Violation]:
    census = primitive_census(closed.jaxpr)
    got = census.get("cond", 0)
    want = contract.expected_conds
    if got >= want:
        return []
    return [Violation(
        rule="GA-J003", file=file, line=line, entrypoint=contract.name,
        message=f"expected >= {want} surviving lax.cond branch(es), found "
                f"{got} (select_n count: {census.get('select_n', 0)}) — a "
                "batched predicate lowered the branch to select_n, so BOTH "
                "sides now execute every call")]


def _check_donation(spec, contract, file, line) -> list[Violation]:
    import jax

    def positional(*dyn):
        return spec.fn(*dyn, **spec.kwargs)

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        lowered = jax.jit(
            positional, donate_argnums=contract.donate).lower(*spec.args)
        text = lowered.as_text()
    unusable = [w for w in caught
                if "donated buffers were not usable" in str(w.message)]
    if "tf.aliasing_output" in text and not unusable:
        return []
    detail = str(unusable[0].message) if unusable else \
        "no tf.aliasing_output annotation in the lowering"
    return [Violation(
        rule="GA-J004", file=file, line=line, entrypoint=contract.name,
        message=f"declared donation of args {contract.donate} does not hold "
                f"in the lowering ({detail}) — the donated buffers would be "
                "copied, not reused")]


def _leaf_fingerprint(tree):
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    out = []
    for leaf in leaves:
        aval = jax.api_util.shaped_abstractify(leaf)
        out.append((tuple(aval.shape), str(aval.dtype),
                    bool(getattr(aval, "weak_type", False))))
    return tuple(out)


def _check_compile_keys(contract, file, line) -> list[Violation]:
    rungs = contract.ladder()
    keys = {}
    for rung in rungs:
        key = (repr(rung.statics), _leaf_fingerprint(rung.dynamic))
        keys.setdefault(key, []).append(rung.name)
    want = contract.expected_compile_keys
    if want is None:
        want = len(rungs)
    if len(keys) == want:
        return []
    detail = "; ".join(",".join(v) for v in keys.values())
    return [Violation(
        rule="GA-J005", file=file, line=line, entrypoint=contract.name,
        message=f"expected {want} distinct compile key(s) across the ladder, "
                f"got {len(keys)} (groups: {detail}) — an aval or weak-type "
                "drift is splitting (or collapsing) the jit cache")]


def _check_feedback(spec, contract, file, line) -> list[Violation]:
    import jax

    out_shapes = jax.eval_shape(spec.thunk())
    violations = []
    for out_get, arg_get in contract.feedback:
        fed = out_get(out_shapes)
        arg = arg_get(spec)
        fed_fp = _leaf_fingerprint(fed)
        arg_fp = _leaf_fingerprint(arg)
        if fed_fp == arg_fp:
            continue
        diffs = [i for i, (a, b) in enumerate(zip(fed_fp, arg_fp)) if a != b]
        if len(fed_fp) != len(arg_fp):
            what = f"leaf count {len(fed_fp)} vs {len(arg_fp)}"
        else:
            i = diffs[0]
            what = f"leaf {i}: out {fed_fp[i]} vs arg {arg_fp[i]}"
        violations.append(Violation(
            rule="GA-J005", file=file, line=line, entrypoint=contract.name,
            message=f"feedback aval drift ({what}) — feeding this output "
                    "back recompiles the entrypoint every iteration"))
    return violations


def audit_contract(contract: EntrypointContract) -> list[Violation]:
    """All static checks for one registered entrypoint."""
    spec = contract.build()
    file, line = _src_anchor(spec.fn)
    violations: list[Violation] = []
    try:
        closed = trace_entrypoint(spec)
    except Exception as e:  # a trace failure is itself a finding
        return [Violation(
            rule="GA-J001", file=file, line=line, entrypoint=contract.name,
            message=f"entrypoint failed to trace abstractly: {e!r}")]
    violations += _check_loop_rules(closed, contract.name, file, line)
    if contract.expected_conds is not None:
        violations += _check_cond_survival(closed, contract, file, line)
    if contract.donate is not None:
        violations += _check_donation(spec, contract, file, line)
    if contract.ladder is not None:
        violations += _check_compile_keys(contract, file, line)
    if contract.feedback:
        violations += _check_feedback(spec, contract, file, line)
    return violations


def audit_contracts(contracts) -> list[Violation]:
    out: list[Violation] = []
    for c in contracts:
        out.extend(audit_contract(c))
    return out


def run_checkify(contracts) -> list[Violation]:
    """Opt-in runtime half: execute each contract's checkify thunk on the
    canonical small config (CONCRETE execution — not part of the static
    gate). A failed check surfaces as a violation with the check message."""
    out: list[Violation] = []
    for c in contracts:
        if c.runtime_check is None:
            continue
        spec = c.build()
        file, line = _src_anchor(spec.fn)
        try:
            c.runtime_check()
        except Exception as e:
            out.append(Violation(
                rule="GA-J005", file=file, line=line, entrypoint=c.name,
                message=f"runtime contract failed: {e}"))
    return out
