"""Leaf-granular read/write sets from jaxprs (the analyzer's foundation).

A MISO transition is a pure function ``prev: dict[cell, state] -> new own
state``.  Tracing it with :func:`jax.make_jaxpr` over abstract
``ShapeDtypeStruct`` inputs (no FLOPs, no buffers) yields a jaxpr whose
invars correspond 1:1 with the flattened leaves of the *full* program
state.  From that we compute, per cell:

  * which leaves of which neighbor states the transition actually
    consumes (a backward liveness walk over the jaxpr, recursing into
    ``pjit``/``scan``/``cond`` sub-jaxprs),
  * which output leaves are genuinely written vs carried over bit-for-bit
    (an output var that *is* the matching own-state input var),
  * which declared ``reads`` are dead (declared, zero leaves consumed).

The liveness walk is deliberately *conservative*: any primitive we do not
model keeps all of its inputs live.  Over-approximating "used" means
undeclared reads are never missed (soundness of MISO001) and dead reads
are never falsely reported (deleting a MISO002 read is always safe).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping

import jax
from jax.extend import core as jcore
from jax.tree_util import keystr, tree_flatten_with_path

Pytree = Any


# ---------------------------------------------------------------------------
# Backward liveness: which invars of a jaxpr feed its live outvars?
# ---------------------------------------------------------------------------


def _subjaxpr(val):
    """Unwrap a params value to a raw Jaxpr if it is one (closed or open)."""
    if isinstance(val, jcore.ClosedJaxpr):
        return val.jaxpr
    if isinstance(val, jcore.Jaxpr):
        return val
    return None


def used_invars(jaxpr: jcore.Jaxpr, live_out: list[bool]) -> list[bool]:
    """Backward data-flow: ``used[i]`` iff invar ``i`` can reach a live
    outvar.  Recurses into pjit/scan/cond sub-jaxprs for precision; any
    unmodeled primitive conservatively keeps all its inputs live."""
    live: set[jcore.Var] = set()
    for var, out_live in zip(jaxpr.outvars, live_out):
        if out_live and isinstance(var, jcore.Var):
            live.add(var)

    for eqn in reversed(jaxpr.eqns):
        eqn_live_out = [isinstance(v, jcore.Var) and v in live for v in eqn.outvars]
        if not any(eqn_live_out):
            continue
        in_used = _eqn_used_invars(eqn, eqn_live_out)
        for var, used in zip(eqn.invars, in_used):
            if used and isinstance(var, jcore.Var):
                live.add(var)

    return [v in live for v in jaxpr.invars]


def _eqn_used_invars(eqn, live_out: list[bool]) -> list[bool]:
    name = eqn.primitive.name
    handler = _LIVENESS_HANDLERS.get(name)
    if handler is not None:
        try:
            return handler(eqn, live_out)
        except Exception:  # malformed params — fall back to conservative
            pass
    # Unmodeled primitive: every input feeds every output.
    return [True] * len(eqn.invars)


def _live_pjit(eqn, live_out):
    sub = _subjaxpr(eqn.params["jaxpr"])
    if sub is None or len(sub.invars) != len(eqn.invars):
        return [True] * len(eqn.invars)
    return used_invars(sub, live_out)


def _live_scan(eqn, live_out):
    """scan body: invars = consts + carry + xs, outvars = carry + ys.
    Carry liveness needs a fixpoint: a live final carry makes the whole
    carry chain live, and carries can feed each other across iterations."""
    sub = _subjaxpr(eqn.params["jaxpr"])
    nc = eqn.params["num_consts"]
    ncar = eqn.params["num_carry"]
    if sub is None or len(sub.invars) != len(eqn.invars):
        return [True] * len(eqn.invars)
    body_live_out = list(live_out)
    used = used_invars(sub, body_live_out)
    while True:
        carry_live = [body_live_out[i] or used[nc + i] for i in range(ncar)]
        if carry_live == body_live_out[:ncar]:
            return used
        body_live_out[:ncar] = carry_live
        used = used_invars(sub, body_live_out)


def _live_cond(eqn, live_out):
    """cond: invars = [index] + operands; each branch takes the operands."""
    branches = eqn.params["branches"]
    n_ops = len(eqn.invars) - 1
    ops_used = [False] * n_ops
    for br in branches:
        sub = _subjaxpr(br)
        if sub is None or len(sub.invars) != n_ops:
            return [True] * len(eqn.invars)
        for i, u in enumerate(used_invars(sub, list(live_out))):
            ops_used[i] = ops_used[i] or u
    return [True] + ops_used


def _live_remat(eqn, live_out):
    sub = _subjaxpr(eqn.params["jaxpr"])
    if sub is None or len(sub.invars) != len(eqn.invars):
        return [True] * len(eqn.invars)
    return used_invars(sub, live_out)


_LIVENESS_HANDLERS: dict[str, Callable] = {
    "pjit": _live_pjit,
    "closed_call": _live_pjit,
    "core_call": _live_pjit,
    "scan": _live_scan,
    "cond": _live_cond,
    "remat": _live_remat,
    "remat2": _live_remat,
    "checkpoint": _live_remat,
    # while/custom_jvp/custom_vjp/pallas_call: conservative default.
}


# ---------------------------------------------------------------------------
# Per-cell access extraction
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OutLeaf:
    """Classification of one output leaf of a transition."""

    path: str  # keystr within the cell state, e.g. "['cache']['pos']"
    kind: str  # "written" | "carried" | "const"
    shape: tuple[int, ...] = ()
    dtype: str = ""


@dataclasses.dataclass
class CellAccess:
    """Exact leaf-granular access sets of one cell's transition."""

    cell: str
    declared: tuple[str, ...]
    #: cell -> leaf paths of that cell's state actually consumed
    reads: dict[str, tuple[str, ...]]
    #: declared reads with zero consumed leaves (false serialization edges)
    dead_reads: tuple[str, ...]
    #: reads of cells absent from {self} | declared (MISO001 material)
    undeclared: tuple[str, ...]
    out_leaves: tuple[OutLeaf, ...]
    closed_jaxpr: jcore.ClosedJaxpr = dataclasses.field(repr=False)

    @property
    def read_cells(self) -> tuple[str, ...]:
        """Cells (beside self) with at least one leaf actually consumed."""
        return tuple(c for c in self.reads if c != self.cell)

    @property
    def carried_leaves(self) -> tuple[str, ...]:
        return tuple(o.path for o in self.out_leaves if o.kind == "carried")

    @property
    def written_leaves(self) -> tuple[str, ...]:
        return tuple(o.path for o in self.out_leaves if o.kind != "carried")

    def to_dict(self) -> dict:
        return {
            "cell": self.cell,
            "declared": list(self.declared),
            "reads": {c: list(ps) for c, ps in self.reads.items()},
            "dead_reads": list(self.dead_reads),
            "undeclared": list(self.undeclared),
            "out_leaves": [dataclasses.asdict(o) for o in self.out_leaves],
        }


class TraceFailure(Exception):
    """The transition could not be abstractly evaluated (MISO004)."""


def trace_cell(cell, specs: Mapping[str, Pytree]) -> CellAccess:
    """Trace ``cell.transition`` against the *full* program state and
    compute its exact leaf-granular access sets.

    ``specs`` maps every cell name to the ShapeDtypeStruct skeleton of its
    state as a transition sees it (``MisoProgram.state_specs()``).  Passing
    the full dict (not the restricted view) is what lets undeclared reads
    surface as data-flow facts instead of KeyErrors.
    """
    full = dict(specs)
    try:
        closed, out_shape = jax.make_jaxpr(cell.transition, return_shape=True)(full)
    except Exception as e:  # noqa: BLE001 — any trace failure is MISO004
        raise TraceFailure(f"{type(e).__name__}: {e}") from e

    in_leaves, _ = tree_flatten_with_path(full)
    jaxpr = closed.jaxpr
    if len(jaxpr.invars) != len(in_leaves):
        raise TraceFailure(
            f"invar/leaf mismatch: {len(jaxpr.invars)} invars vs "
            f"{len(in_leaves)} input leaves"
        )

    # invar index -> (cell name, leaf path within that cell's state)
    leaf_of: list[tuple[str, str]] = []
    for path, _leaf in in_leaves:
        leaf_of.append((path[0].key, keystr(path[1:])))

    used = used_invars(jaxpr, [True] * len(jaxpr.outvars))

    reads: dict[str, list[str]] = {}
    for (cname, lpath), u in zip(leaf_of, used):
        if u:
            reads.setdefault(cname, []).append(lpath)

    declared = tuple(cell.reads)
    allowed = {cell.name, *declared}
    undeclared = tuple(sorted(c for c in reads if c not in allowed))
    dead = tuple(c for c in declared if c not in reads)

    # Output leaf classification: an outvar that *is* the invar of the
    # matching own-state leaf was carried over bit-for-bit.
    own_invar: dict[str, jcore.Var] = {}
    for (cname, lpath), var in zip(leaf_of, jaxpr.invars):
        if cname == cell.name:
            own_invar[lpath] = var
    out_paths = [keystr(path) for path, _ in tree_flatten_with_path(out_shape)[0]]
    out_leaves = []
    for path, var, aval in zip(out_paths, jaxpr.outvars, closed.out_avals):
        if isinstance(var, jcore.Literal):
            kind = "const"
        elif own_invar.get(path) is var:
            kind = "carried"
        else:
            kind = "written"
        out_leaves.append(
            OutLeaf(
                path=path,
                kind=kind,
                shape=tuple(aval.shape),
                dtype=str(aval.dtype),
            )
        )

    return CellAccess(
        cell=cell.name,
        declared=declared,
        reads={c: tuple(ps) for c, ps in reads.items()},
        dead_reads=dead,
        undeclared=undeclared,
        out_leaves=tuple(out_leaves),
        closed_jaxpr=closed,
    )
