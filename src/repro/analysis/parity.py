"""Parity-hazard lints: what silently breaks bitwise DMR/TMR (§IV).

The dependability contract of the whole repo is *bitwise* replica
equality: every subsystem's tests compare replicas with ``state_hash`` or
exact array equality.  Two classes of transition code break that contract
without ever raising:

  * **Replica-variant PRNG** (MISO101).  A replicated cell's transition
    draws randomness from a key derived only from compile-time constants.
    Every replica then draws the *same* stream every step — the stream is
    not threaded through the replicated state, so it never diverges per
    replica *and* it repeats identically across transitions, making the
    "random" draw a constant and any fault in it undetectable by replica
    comparison.  The blessed pattern is the data cell's: keep the key in
    the cell state and ``jax.random.split`` it each transition.
  * **Order-sensitive accumulation** (MISO102).  ``scatter-add``/``mul``
    with ``unique_indices=False`` accumulates in an order XLA does not
    fix across backends/replica placements; float non-associativity then
    produces replica-divergent bits.

Both are found by a forward constant-taint walk over the jaxpr: a value
is *const-tainted* iff it derives only from literals/constants (never
from the transition's state inputs).  The walk recurses into
pjit/scan/cond sub-jaxprs and visits every PRNG/scatter equation on the
way.
"""

from __future__ import annotations

from typing import Callable

from jax.extend import core as jcore

from .access import CellAccess, _subjaxpr
from .diagnostics import Diagnostic

#: primitive name -> indices of its *key* operands (const key => MISO101)
_PRNG_KEY_OPERANDS = {
    "threefry2x32": (0, 1),
    "random_bits": (0,),
    "random_fold_in": (0,),
    "random_seed": (0,),
}

_ACCUM_SCATTERS = {"scatter-add", "scatter-mul"}


def _taint_walk(jaxpr: jcore.Jaxpr, in_const: list[bool], visit) -> list[bool]:
    """Forward const-taint: returns per-outvar taint; calls
    ``visit(eqn, invar_taints)`` on every equation, recursively."""
    taint: dict[jcore.Var, bool] = {v: True for v in jaxpr.constvars}
    for v, t in zip(jaxpr.invars, in_const):
        taint[v] = t

    def tof(atom) -> bool:
        if isinstance(atom, jcore.Literal):
            return True
        return taint.get(atom, True)

    for eqn in jaxpr.eqns:
        in_taints = [tof(v) for v in eqn.invars]
        visit(eqn, in_taints)
        out_taints = _eqn_out_taints(eqn, in_taints, visit)
        for v, t in zip(eqn.outvars, out_taints):
            if isinstance(v, jcore.Var):
                taint[v] = t

    return [tof(v) for v in jaxpr.outvars]


def _eqn_out_taints(eqn, in_taints: list[bool], visit) -> list[bool]:
    name = eqn.primitive.name
    handler = _TAINT_HANDLERS.get(name)
    if handler is not None:
        try:
            return handler(eqn, in_taints, visit)
        except Exception:  # malformed params — conservative: not const
            return [False] * len(eqn.outvars)
    # Default: outputs are const iff every input is.
    return [all(in_taints)] * len(eqn.outvars)


def _taint_pjit(eqn, in_taints, visit):
    sub = _subjaxpr(eqn.params["jaxpr"])
    if sub is None or len(sub.invars) != len(eqn.invars):
        return [False] * len(eqn.outvars)
    return _taint_walk(sub, in_taints, visit)


def _taint_scan(eqn, in_taints, visit):
    """Fixpoint over the carry: taint can only decay False, so iterating
    the body with fed-back carry taints terminates."""
    sub = _subjaxpr(eqn.params["jaxpr"])
    nc = eqn.params["num_consts"]
    ncar = eqn.params["num_carry"]
    if sub is None or len(sub.invars) != len(eqn.invars):
        return [False] * len(eqn.outvars)
    body_in = list(in_taints)
    while True:
        # Visit only on the converged pass (below) to avoid duplicates.
        out = _taint_walk(sub, body_in, lambda *_: None)
        new_carry = [body_in[nc + i] and out[i] for i in range(ncar)]
        if new_carry == body_in[nc : nc + ncar]:
            break
        body_in[nc : nc + ncar] = new_carry
    out = _taint_walk(sub, body_in, visit)
    return out


def _taint_cond(eqn, in_taints, visit):
    branches = eqn.params["branches"]
    n_ops = len(eqn.invars) - 1
    outs = None
    for br in branches:
        sub = _subjaxpr(br)
        if sub is None or len(sub.invars) != n_ops:
            return [False] * len(eqn.outvars)
        o = _taint_walk(sub, in_taints[1:], visit)
        outs = o if outs is None else [a and b for a, b in zip(outs, o)]
    return outs if outs is not None else [False] * len(eqn.outvars)


def _taint_remat(eqn, in_taints, visit):
    sub = _subjaxpr(eqn.params["jaxpr"])
    if sub is None or len(sub.invars) != len(eqn.invars):
        return [False] * len(eqn.outvars)
    return _taint_walk(sub, in_taints, visit)


_TAINT_HANDLERS: dict[str, Callable] = {
    "pjit": _taint_pjit,
    "closed_call": _taint_pjit,
    "core_call": _taint_pjit,
    "scan": _taint_scan,
    "cond": _taint_cond,
    "remat": _taint_remat,
    "remat2": _taint_remat,
    "checkpoint": _taint_remat,
}


def lint_cell(cell, access: CellAccess, program: str = "") -> list[Diagnostic]:
    """Parity-hazard lints over one traced cell.

    MISO101 fires only for replicated cells (level >= 2): an unreplicated
    cell is free to use deterministic constant-key draws (the data
    pipeline's bigram table is the in-repo example); with replicas the
    same pattern silently voids the §IV comparison.
    """
    diags: list[Diagnostic] = []
    replicated = cell.redundancy.level > 1
    const_draws: list[str] = []
    unordered_accums: list[str] = []

    def visit(eqn, in_taints):
        name = eqn.primitive.name
        key_ops = _PRNG_KEY_OPERANDS.get(name)
        if key_ops is not None and all(in_taints[i] for i in key_ops):
            const_draws.append(name)
        if name in _ACCUM_SCATTERS and not eqn.params.get("unique_indices", False):
            unordered_accums.append(name)

    jaxpr = access.closed_jaxpr.jaxpr
    _taint_walk(jaxpr, [False] * len(jaxpr.invars), visit)

    if replicated and const_draws:
        diags.append(
            Diagnostic(
                code="MISO101",
                program=program,
                cell=cell.name,
                message=(
                    f"replicated cell {cell.name!r} (level "
                    f"{cell.redundancy.level}) draws randomness from a "
                    f"compile-time-constant PRNG key "
                    f"({len(const_draws)} draw(s): "
                    f"{sorted(set(const_draws))})"
                ),
                notes=(
                    "every replica draws the identical stream every step: "
                    "the draw is a constant and replica comparison cannot "
                    "cover it",
                    "thread the key through the cell state and "
                    "jax.random.split it each transition (see "
                    "repro.data.pipeline for the pattern)",
                ),
                data={"draws": sorted(set(const_draws))},
            )
        )
    if replicated and unordered_accums:
        diags.append(
            Diagnostic(
                code="MISO102",
                program=program,
                cell=cell.name,
                message=(
                    f"replicated cell {cell.name!r} accumulates with "
                    f"{sorted(set(unordered_accums))} and "
                    f"unique_indices=False: accumulation order is "
                    f"backend-chosen, so float non-associativity can "
                    f"diverge replicas bitwise"
                ),
                notes=(
                    "pass unique_indices=True when indices are provably "
                    "unique, or restructure to a segment-sum with a fixed "
                    "order",
                ),
                data={"primitives": sorted(set(unordered_accums))},
            )
        )
    return diags
