"""Shared jaxpr walker for the structural tests (gather/transpose/dot counts)."""

from jax.extend.core import ClosedJaxpr, Jaxpr


def all_eqns(jaxpr):
    """All eqns of a jaxpr, recursing into call/branch/scan sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from all_eqns(sub)


def _sub_jaxprs(v):
    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _sub_jaxprs(x)


def pallas_kernels(jaxpr):
    """Names of the kernel functions of every ``pallas_call`` in a jaxpr."""
    return [eqn.params["jaxpr"].debug_info.func_src_info.split()[0]
            for eqn in all_eqns(jaxpr) if eqn.primitive.name == "pallas_call"]
