"""Model: own time a step of the device ops under the scope `attn` that
are not the cores': the norm before the projections, the q, k, v and output
projections, the rotary rule (`rope`) and the per-head gate (`attn_gate`),
forward and backward: `attn` less `window_core_ms` and `full_core_ms`.
Device trace over the step program's scope table, milliseconds."""

from benchmark.families import laguna


def read(record, trace):
    whole = laguna.scope_own_ms(
        record, trace, {"attn"} - set(laguna.CORE_SCOPES.values()))
    cores = [laguna.core_ms(record, trace, which) for which in laguna.CORE_SCOPES]
    if whole is None or None in cores:
        return None
    return whole - sum(cores)
