"""What a traced function is made of: its primitives, through every nested
jaxpr (custom-VJP bodies, ``checkpoint`` replays, ``pjit`` calls)."""

import jax


def _walk(jaxpr, found):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            name = eqn.params["name"]
        found.append((name, [getattr(v.aval, "shape", ()) for v in eqn.invars]))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _walk(sub, found)
    return found


def primitives(fn, *args):
    """[(primitive name — a ``pallas_call`` by its kernel's ``name=`` —,
    shapes of its operands)] of ``fn(*args)``'s jaxpr."""
    return _walk(jax.make_jaxpr(fn)(*args).jaxpr, [])
