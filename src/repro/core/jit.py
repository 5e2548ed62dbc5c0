"""``jax.jit`` that hands back unchanged inputs instead of copying them."""
from __future__ import annotations

import collections
from typing import Any, Callable

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
    jaxpr; the compiled program returns only the others.

    ``call(*args, donate=None)``: ``donate`` is a prefix of ``args`` with
    bool leaves.  The inputs under True that the program computes anew
    (never one it passes through, nor one buffer passed twice) are
    donated: the program may write its results over them, and the call
    deletes them.  The default donates nothing.  ``call.lower(*args,
    donate=None)`` lowers the program that call would run."""
    plans: dict = {}

    def program(args, donate):
        """``args`` flattened, which outputs pass which input through, the
        output structure, and the program that donates what ``donate``
        asks for."""
        flat, in_tree = jax.tree.flatten(args)
        key = (in_tree, tuple(jax.typeof(x) for x in flat))
        plan = plans.get(key)
        if plan is None:
            closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
            pos = {id(v): i for i, v in enumerate(closed.jaxpr.invars)}
            fwd = [pos.get(id(v)) for v in closed.jaxpr.outvars]

            def computed(*leaves):
                outs = jax.tree.leaves(fn(*jax.tree.unflatten(in_tree, leaves)))
                return [o for o, f in zip(outs, fwd) if f is None]

            plan = plans[key] = (fwd, jax.tree.structure(out_shape),
                                 named(computed, name), {})
        fwd, out_tree, computed, by_donation = plan
        gives = ()
        if donate is not None:
            uses = collections.Counter(map(id, flat))
            flags = jax.tree.leaves(jax.tree.broadcast(donate, args))
            gives = tuple(i for i, (x, d) in enumerate(zip(flat, flags))
                          if d and i not in fwd and uses[id(x)] == 1)
        jitted = by_donation.get(gives)
        if jitted is None:
            jitted = by_donation[gives] = jax.jit(computed, donate_argnums=gives)
        return flat, fwd, out_tree, jitted

    def call(*args, donate: Any = None):
        flat, fwd, out_tree, jitted = program(args, donate)
        got = iter(jitted(*flat))
        return jax.tree.unflatten(
            out_tree, [next(got) if f is None else flat[f] for f in fwd])

    def lower(*args, donate: Any = None):
        flat, _, _, jitted = program(args, donate)
        return jitted.lower(*flat)

    call.lower = lower
    return call
