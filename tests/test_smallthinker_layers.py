"""The mechanisms SmallThinker-21BA3B-Instruct brought to
`models/transformer.py` (PR 65), each held on its own at one period of the
family's small model (`family_cases.SMALLTHINKER_ONE_PERIOD`): the four shares
of a layer routed ahead of its mixer add up to the uncut reference's layer; a
layer that is run again keeps the order of its token-choices and does not sort
twice; the share of the relu gates that is exactly zero, and its gauge;
positions as a layer's own; and the programs of every other configuration
hold none of it."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_cases import (HELD_LOADS, SMALLTHINKER_ONE_PERIOD,
                          held_load_is_the_references, refused, shares_add_up)
from kungfu_tpu.models import transformer
from kungfu_tpu.models.transformer import TransformerConfig
from kungfu_tpu.telemetry import metrics

FAMILY = SMALLTHINKER_ONE_PERIOD
family, tiny_config, CONFIG = FAMILY.module, FAMILY.tiny_config, FAMILY.config


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_reference():
    """One expert layer of 64 relu-gated experts, 6 a token by softmax scores
    renormalised, routed from rows h that are not the rows m the experts
    transform, cut into the deployment's four shares of 16 (experts 0-15,
    16-31, 32-47, 48-63; `family_cases.shares_add_up`)."""
    from benchmark.reference import smallthinker as reference

    E, D, F, T = 64, 16, 8, 24
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    h, m = (jax.random.normal(k, (T, D)) for k in ks[:2])
    w = {"router": 0.5 * jax.random.normal(ks[2], (D, E)),
         "w_gate": 0.3 * jax.random.normal(ks[3], (E, D, F)),
         "w_up": 0.3 * jax.random.normal(ks[4], (E, D, F)),
         "w_down": 0.3 * jax.random.normal(ks[5], (E, F, D))}
    chosen, gates = reference.routing(h, w["router"], 6)
    # every expert over every row, weighed by its gate: the reference's sum
    pre = jnp.einsum("td,edf->etf", m, w["w_gate"])
    each = jnp.einsum("etf,efd->etd", jnp.maximum(pre, 0) * jnp.einsum(
        "td,edf->etf", m, w["w_up"]), w["w_down"])
    weight = jnp.sum(jnp.where(chosen[None] == jnp.arange(E)[:, None, None],
                               gates[None], 0.0), axis=-1)
    want = jnp.einsum("et,etd->td", weight, each)
    np.testing.assert_allclose(
        reference.experts(m, chosen, gates, {k: v[:16] for k, v in w.items()}, 0),
        jnp.einsum("et,etd->td", weight[:16], each[:16]), rtol=1e-4, atol=1e-5)
    cfg = TransformerConfig(d_model=D, d_ff=F, dtype=jnp.float32, ffn="moe",
                            n_experts=E, top_k=6, gates="renorm",
                            expert_act="reglu", router_input="layer")
    assert shares_add_up(m, w, cfg, 16, want, chosen, 0.0, routed_from=h) == 4
    # routed from the rows it transforms it is another layer
    late, _ = reference.routing(m, w["router"], 6)
    assert (np.asarray(late) != np.asarray(chosen)).any()


@pytest.mark.parametrize("load", HELD_LOADS)
def test_the_model_is_the_reference_whatever_the_held_experts_get(load):
    held_load_is_the_references(FAMILY, load)


def _sorts(text: str) -> int:
    """How often the program sorts: `stablehlo.sort` where it stands, times
    the calls of the function that holds it (`jnp.argsort` is one)."""
    total = 0
    for body in text.split("func.func ")[1:]:
        if "stablehlo.sort" in body:
            name = re.match(r"(?:\w+ )?@([\w.]+)", body).group(1)
            total += body.count("stablehlo.sort") * max(
                1, len(re.findall(rf"call @{re.escape(name)}\(", text)))
    return total


def test_the_plan_kept_by_name_is_used_by_the_recomputed_layer(monkeypatch,
                                                               fresh_traces):
    """The lowered step of layers that are run again sorts the token-choices
    once a stack, in the forward scan: the backward scan's copy of the layer
    reads the order kept under `moe_plan`. With the name off the list of what
    `_layer_again` keeps, every stack sorts twice."""
    assert CONFIG["recomputed_layer_types"] == [family.FULL, family.WINDOW]
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))

    def lowered():
        return jax.jit(jax.grad(family.loss_fn(CONFIG))).lower(
            state, FAMILY.sample()).as_text(debug_info=True)

    text = lowered()
    assert _sorts(text) == 2  # one a stack: the full layer's, the window layers'
    assert "moe/moe_plan/" in text and "moe/moe_early_router/" in text
    assert "rematted_computation/moe/moe_early_router/" in text  # the scores, again
    monkeypatch.setattr(transformer, "_layer_again", jax.checkpoint(
        transformer._layer, static_argnums=(2,), prevent_cse=False,
        policy=jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse")))
    jax.clear_caches()
    assert _sorts(lowered()) == 4


def test_the_zero_share_of_the_gates_and_its_gauge():
    """`gate_zero_shares` against the reference's pieces by hand in the first
    layer: the share of relu(W_gate,e m) that is exactly 0 over the rows that
    chose a held expert e; about a half on the trained-like state; and its
    gauge beside the routing's."""
    from benchmark.reference import smallthinker as reference

    config = tiny_config(num_hidden_layers=2, rope_layout=[0, 1],
                         sliding_window_layout=[0, 1])  # a layer of each kind
    mc = family.model_config(config)
    state, sample = family.init(config, 3), FAMILY.sample()
    got = family.gate_zero_shares(config, state, sample)
    assert len(got) == 2 and all(0.3 < share < 0.7 for share in got)
    hyper = family._hyper(config)
    h = state["embed"][sample[:, :-1]]
    w = jax.tree.map(lambda leaf: leaf[0], state["layers"][0])  # layer 0, full
    chosen, _ = reference.routing(h.reshape(-1, 64), w["router"], 3)
    u = reference._rms(h, w["ln1_scale"], hyper["eps"])
    heads = lambda t, n: t.reshape(2, 64, n, 16).transpose(0, 2, 1, 3)
    ctx = reference._attention(heads(u @ w["wq"], 4), heads(u @ w["wk"], 2),
                               heads(u @ w["wv"], 2), 0, 16)
    mid = h + ctx.transpose(0, 2, 1, 3).reshape(2, 64, 64) @ w["wo"]
    m = reference._rms(mid, w["ln2_scale"], hyper["eps"]).reshape(-1, 64)
    zero = rows = 0
    for e in range(4):
        mine = np.asarray((chosen == 2 + e).any(-1))
        pre = np.asarray(m @ w["w_gate"][e])[mine]
        zero, rows = zero + (pre <= 0).sum(), rows + pre.size
    assert got[0] == pytest.approx(zero / rows, abs=2e-3)
    full = jax.jit(lambda p, t: transformer.routing_stats(p, t, mc))(
        state, sample[:, :-1])
    registry = metrics.Registry()
    transformer.record_routing({**full, "gate_zero_share": got}, registry)
    text = registry.render()
    for layer in range(2):
        assert f'kungfu_moe_gate_zero_share{{layer="{layer}"}} 0.' in text
    plain = metrics.Registry()
    transformer.record_routing(full, plain)
    assert "kungfu_moe_gate_zero_share" not in plain.render()
    with pytest.raises(ValueError, match="relu-gated"):
        transformer.gate_zero_shares(
            {}, sample[:, :-1], TransformerConfig(vocab_size=8, d_model=8))


def test_positions_are_a_layers_own():
    """A layer kind says 'rope' or 'none' for its layers; the learned table
    is the embedding's and no layer's; the length limit holds where any layer
    is rotary."""
    refused("added to the embedding once", n_layers=2, layer_kinds=(
        (("positions", "rope"),), (("positions", "learned"),)), positions="rope")
    refused("added to the embedding once", n_layers=2, layer_kinds=(
        (("positions", "rope"),), (("positions", "none"),)))  # under "learned"
    mixed = TransformerConfig(positions="none", n_layers=2, max_seq=16, layer_kinds=(
        (("positions", "none"),), (("positions", "rope"),)))
    assert mixed.rotary and not TransformerConfig(positions="none").rotary
    params = jax.eval_shape(lambda: transformer.init_transformer(
        jax.random.PRNGKey(0), mixed))
    assert "pos_embed" not in params
    with pytest.raises(ValueError, match="exceeds max_seq"):
        transformer._embed({"embed": jnp.zeros((8, 512))},
                           jnp.zeros((1, 32), jnp.int32), mixed)
    refused("not one of", router_input="mixer")
    refused("relu-gated shared expert", expert_act="reglu", shared_ff=64,
               ffn="moe", n_experts=4, top_k=2)


def test_without_the_new_fields_a_lowered_step_is_what_it_was():
    """`router_input` "ffn" is a Python branch: an OLMoE layer's program
    holds none of the new scopes, and its sort is where it was."""
    cfg = TransformerConfig.tiny_moe()
    params = jax.eval_shape(lambda: transformer.init_transformer(
        jax.random.PRNGKey(0), cfg))
    batch = jnp.zeros((2, 65), jnp.int32)
    text = jax.jit(jax.grad(lambda p: transformer.transformer_loss(
        p, batch, cfg))).lower(params).as_text(debug_info=True)
    assert "moe/moe_dispatch/" in text and "moe/moe_router/" in text
    for scope in ("moe_early_router", "moe_plan"):
        assert scope not in text, scope
