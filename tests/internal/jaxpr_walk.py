"""What a traced function is made of: its primitives, through every nested
jaxpr (custom-VJP bodies, ``checkpoint`` replays, ``pjit`` calls)."""

import jax


def equations(jaxpr):
    """Every equation of a jaxpr, those of its nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub)


def _walk(jaxpr, found):
    for eqn in equations(jaxpr):
        name = eqn.primitive.name
        if name == "pallas_call":
            name = eqn.params["name"]
        found.append((name, [getattr(v.aval, "shape", ()) for v in eqn.invars]))
    return found


def primitives(fn, *args):
    """[(primitive name — a ``pallas_call`` by its kernel's ``name=`` —,
    shapes of its operands)] of ``fn(*args)``'s jaxpr."""
    return _walk(jax.make_jaxpr(fn)(*args).jaxpr, [])
