"""What more than one test file reads off a jaxpr, and how more than one
runs the kernels' path on the CPU."""

import functools

from jax import lax

from kungfu_tpu.telemetry import device


def primitives(jaxpr) -> set:
    """The names of the primitives a jaxpr stages, its inner ones' too."""
    return {eqn.primitive.name for eqn in jaxpr.eqns}.union(
        *(primitives(sub) for eqn in jaxpr.eqns for sub in device._sub_jaxprs(eqn)))


def interpret_kernels(m, module, names) -> None:
    """Under the `pytest.MonkeyPatch` `m`: `module`'s kernel builders `names`
    interpreted, and `lax.platform_dependent` (one function for every
    module) taking its TPU branch whatever the platform."""
    for name in names:
        m.setattr(module, name, functools.partial(getattr(module, name), interpret=True))
    m.setattr(lax, "platform_dependent", lambda *args, tpu, default: tpu(*args))


def pallas_calls(jaxpr, recomputed=False):
    """[(kernel's function, inside a checkpoint's recomputed part?)] of every
    `pallas_call` of a jaxpr, through every equation that holds one."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["jaxpr"].debug_info.func_name, recomputed))
        for sub in device._sub_jaxprs(eqn):
            found += pallas_calls(sub, recomputed or eqn.primitive.name == "remat2")
    return found


def pallas_operands(jaxpr) -> dict:
    """{kernel's function: how many operands its `pallas_call` takes} over a
    jaxpr, through every equation that holds one."""
    found = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["jaxpr"].debug_info.func_name] = len(eqn.invars)
        for sub in device._sub_jaxprs(eqn):
            found.update(pallas_operands(sub))
    return found
