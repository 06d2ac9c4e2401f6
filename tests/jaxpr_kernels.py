"""Count the Pallas kernels of a traced function by the name the program
gives them (``pallas_call(name=)``), sub-jaxprs included: the remat of a
block, a custom_vjp's rules and a shard_map each hold their own."""

import collections


def pallas_calls(value, found=None) -> collections.Counter:
    """{kernel name: calls} over a Jaxpr, a ClosedJaxpr, or any tuple or
    list of an equation's parameters that holds some."""
    found = collections.Counter() if found is None else found
    if isinstance(value, (tuple, list)):
        for v in value:
            pallas_calls(v, found)
    jaxpr = getattr(value, "jaxpr", value)      # a ClosedJaxpr's own
    for eqn in getattr(jaxpr, "eqns", ()):
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1      # the kernel's body holds none
        else:
            pallas_calls(list(eqn.params.values()), found)
    return found
