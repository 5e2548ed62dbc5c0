"""``jax.jit`` that hands back unchanged inputs instead of copying them."""
from __future__ import annotations

from typing import Callable

import jax


def named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``: the name ``jax.jit`` gives its program
    (``jit_<name>`` in a profile)."""

    def call(*args, **kwargs):
        return fn(*args, **kwargs)

    call.__name__ = call.__qualname__ = name
    return call


def forwarding_jit(fn: Callable, *, name: str) -> Callable:
    """``jax.jit(fn)``, except that every output leaf ``fn`` returns
    unchanged from its inputs comes back as the input array itself.
    The compiled program is called ``name``.

    A plain jit writes every output to a fresh buffer.  A program's static
    cells (the serving weights) and the cells a slot operation leaves alone
    would then be copied by every step: a second copy in device memory and
    a full read and write of it per call.  Which outputs pass an input
    through is read once per input structure and shape from ``fn``'s
    jaxpr; the compiled program returns only the others."""
    plans: dict = {}

    def call(*args):
        flat, in_tree = jax.tree.flatten(args)
        key = (in_tree, tuple(jax.typeof(x) for x in flat))
        plan = plans.get(key)
        if plan is None:
            closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
            pos = {id(v): i for i, v in enumerate(closed.jaxpr.invars)}
            fwd = [pos.get(id(v)) for v in closed.jaxpr.outvars]

            def computed(*a):
                outs = jax.tree.leaves(fn(*a))
                return [o for o, f in zip(outs, fwd) if f is None]

            plan = plans[key] = (fwd, jax.tree.structure(out_shape),
                                 jax.jit(named(computed, name)))
        fwd, out_tree, jitted = plan
        got = iter(jitted(*args))
        return jax.tree.unflatten(
            out_tree, [next(got) if f is None else flat[f] for f in fwd])

    return call
