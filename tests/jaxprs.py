"""What more than one test file reads off a jaxpr."""

from kungfu_tpu.telemetry import device


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
