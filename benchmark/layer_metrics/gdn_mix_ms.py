"""Model: own time a step of the device ops under the scope `gdn` that are
not the delta rule's: the norm before the mixer, the fused q, k, v, z
projection and the float32 one of the decay and the write strength
(`gdn_proj`), the causal convolution, its silu and the q/k normalisation
(`gdn_conv`), the gated norm (`gdn_norm`) and the output projection,
forward and backward: `gdn` less `gdn_core_ms`. Device trace over the step
program's scope table, milliseconds."""

from benchmark.families import qwen3_next


def read(record, trace):
    core_scope = qwen3_next.CORE_SCOPES[qwen3_next.LINEAR]
    whole = qwen3_next.scope_own_ms(record, trace, {"gdn", core_scope})
    core = qwen3_next.core_ms(record, trace, qwen3_next.LINEAR)
    if whole is None or core is None:
        return None
    return whole - core
