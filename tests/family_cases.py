"""What the tests of every model family on `models/transformer.py` share (PR
47): the record a family gives, what is computed from it once a process and
kept, and the cases written once over that record. A family's file
(`tests/test_<family>.py`, and `tests/test_<family>_faults.py` so that the
suite's workers share the compiles) takes its record from here, gives it what
the cases ask (`Family.with_cases`), imports the cases with
`pytest_generate_tests` and keeps the cases of its own mechanism;
`tests/test_family_cases.py` holds every family to the whole set. A
`model_config` PR adds a record and its own cases, not a copy."""

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from benchmark import harness, manifest as mf
from benchmark.families import (glm4_moe_lite, granite_hybrid, keye_vl2,
                                kimi_linear, laguna, lfm2_moe, nemotron_h, ouro,
                                qwen3_next, smallthinker, xing4_0)
from kungfu_tpu.models import transformer
from kungfu_tpu.models.transformer import param_pspecs
from kungfu_tpu.ops import gated_norm, moe
from kungfu_tpu.telemetry import metrics


@dataclasses.dataclass(eq=False)
class Family:
    """A model family as its tests see it: its cell, its `benchmark.families`
    module, the cell's configuration in small, and how a state "as after some
    training" is made of the initial one (every leaf named in `scales` times
    its factor; normal noise of size 0.4 on every norm named in `norms`,
    drawn under `norm_key` of the stack's and the norm's numbers, and 0.3 on
    the last norm; `trained_more(family, state, key)` for what only this
    family has). Then what the shared cases ask, some of it of its file."""
    name: str
    cell: str
    module: object
    tiny: dict
    scales: dict
    norms: tuple
    norm_key: Callable = lambda key, s, i: jax.random.fold_in(
        jax.random.fold_in(key, 10 + s), i)
    seed: int = 5
    configured: Callable = None  # the tiny configuration, edited in place
    trained_more: Callable = None
    sampled: Callable = None  # what a family makes of its drawn sample
    expert_layers: tuple = ()  # the model's layers that route, a module's last
    held_share: tuple = ()  # bounds of the held experts' share of the choices
    scopes: tuple = ()  # what the cell's per-layer metrics read

    float32_grad_rtol: float = 1e-4
    constants: tuple = ()  # leaves that are constants of the loss
    recomputed: tuple = ()  # lists of layer kinds to run again, each a case
    recomputed_rtol: tuple = (1e-6, 1e-5)  # of the loss, of the gradients
    named_specs: Callable = None  # the family's own assertions on its specs
    tp_leaf: tuple = ()  # the path of a leaf that a `tp` of two divides
    faults: dict = None  # name -> what it patches, given a MonkeyPatch
    state_faults: dict = dataclasses.field(default_factory=dict)

    def with_cases(self, **data) -> "Family":
        """This record (one a family and process: what is kept is kept once)."""
        vars(self).update(data)
        return self

    def tiny_config(self, **changes) -> dict:
        config = mf.cell(mf.load(), self.cell)["config"]  # read anew
        config.update(self.tiny)
        if self.configured:
            self.configured(config)
        config.update(changes)
        return config

    @functools.cached_property
    def config(self) -> dict:
        return self.tiny_config()

    def trained(self, layer: dict, key, s: int) -> dict:
        """Stack s, or a layer numbered as one, as after some training."""
        layer = {name: leaf * self.scales.get(name, 1.0)
                 for name, leaf in layer.items()}
        for i, name in enumerate(self.norms):
            if name in layer:
                layer[name] = layer[name] + 0.4 * jax.random.normal(
                    self.norm_key(key, s, i), layer[name].shape)
        return layer

    @functools.cache
    def state(self):
        """A state as after some training, so that no fault can hide behind
        the initial values: norms' scales off their start, sharp attention,
        gates off one half, routers with preferences, experts that weigh."""
        state = self.module.init(self.config, self.seed)
        key = jax.random.PRNGKey(self.seed + 100)
        stacks = state["layers"]  # a tuple of stacks, or the one stack
        state = {**state,
                 "layers": (self.trained(stacks, key, 0)
                            if isinstance(stacks, dict) else
                            tuple(self.trained(stack, key, s)
                                  for s, stack in enumerate(stacks))),
                 "ln_f_scale": state["ln_f_scale"] + 0.3 * jax.random.normal(
                     key, state["ln_f_scale"].shape)}
        return self.trained_more(self, state, key) if self.trained_more else state

    @functools.cache
    def sample(self):
        batch = self.module.host_batch(self.config, self.seed, 0, 2)
        return self.sampled(self, batch) if self.sampled else batch

    @functools.cache
    def reference(self):
        """The reference's loss and gradients on `state()` and `sample()`."""
        return self.module.reference_loss_and_grads(
            self.config, self.state(), self.sample())

    @functools.cache
    def lowered(self) -> str:
        """The program's text at `config`, with its scopes."""
        state = jax.eval_shape(lambda: self.module.init(self.config, 0))
        return self.module.program_loss_and_grads(self.config).lower(
            state, self.sample()).as_text(debug_info=True)

    @functools.cache
    def program(self):
        """The jitted program at `config`, for a case that runs it on
        several states. Never called under a patch."""
        return self.module.program_loss_and_grads(self.config)

    @functools.cache
    def baseline(self, recomputed=None):
        """The program's loss and gradients on `state()` and `sample()`, at
        `config` or with these layer kinds run again in its place: arrays,
        which `fresh_traces` does not cost. Never called under a patch."""
        config = self.config if recomputed is None else self.tiny_config(
            recomputed_layer_types=list(recomputed))
        return self.module.program_loss_and_grads(config)(
            self.state(), self.sample())


def _laguna_yarn(config):
    # YaRN over 8 rotated features of 16: the pairs blend between 0 and 1
    config["rope_parameters"]["full_attention"].update(
        original_max_position_embeddings=32, factor=8)


def _glm_module(family, state, key):
    """A projection of the module that mixes both of its halves, its block
    as a layer of the stack, its norms off one."""
    mtp = state["mtp"]
    mtp = {**mtp, "eh_proj": 4.0 * mtp["eh_proj"],
           "layer": family.trained(mtp["layer"], key, 10),
           **{name: mtp[name] + 0.4 * jax.random.normal(
               jax.random.fold_in(key, 30 + i), mtp[name].shape)
              for i, name in enumerate(("enorm_scale", "hnorm_scale", "ln_f_scale"))}}
    return {**state, "mtp": mtp}


def _nemotron_memory(family, state, key):
    """In a Mamba-2 layer a memory of 2 to 25 positions (A in [0.05, 0.5]
    under steps near 0.8) in the place of the start's few, a D off 1."""
    def remembering(stack, key):
        if "A_log" not in stack:
            return stack
        shape = stack["A_log"].shape
        return {**stack,
                "A_log": jnp.log(jax.random.uniform(
                    jax.random.fold_in(key, 7), shape, minval=0.05, maxval=0.5)),
                "dt_bias": jax.random.uniform(
                    jax.random.fold_in(key, 8), shape, minval=0.1, maxval=0.5),
                "D_skip": 1.0 + 0.5 * jax.random.normal(
                    jax.random.fold_in(key, 9), shape)}

    return {**state, "layers": tuple(
        remembering(stack, jax.random.fold_in(key, 10 + s))
        for s, stack in enumerate(state["layers"]))}


_EXPERTS = {name: 5.0 for name in ("w_gate", "w_up", "w_down", "shared_gate",
                                   "shared_up", "shared_down")}

# five layers as the cell's: full + dense, three sliding + experts, full +
# experts; 4 and 6 query heads on 2 key/value heads, window 16 of 64 positions,
# 16 experts of which numbers 4 to 7 are held, 3 a token, a shared expert
LAGUNA = Family(
    name="laguna", cell="laguna_s_2_1.ssgd_1seq_1chip", module=laguna,
    tiny=dict(hidden_size=64, intermediate_size=96, head_dim=16,
              num_attention_heads=4, num_key_value_heads=2,
              num_attention_heads_per_layer=[4, 6, 6, 6] * 12, sliding_window=16,
              num_experts=4, first_expert_held=4, num_experts_per_tok=3,
              moe_intermediate_size=32, shared_expert_intermediate_size=32,
              published={"num_experts": 16}, vocab_size=256, sequence_length=64,
              flash_blocks=[16, 16], flash_interpret=True,
              compute_dtype="float32"),
    configured=_laguna_yarn,
    scales={"wq": 6.0, "wk": 6.0, "wv": 3.0, "w_head_gate": 30.0,
            "router": 20.0, **_EXPERTS},
    norms=("ln1_scale", "ln2_scale"), expert_layers=(1, 2, 3, 4),
    held_share=(0.1, 0.4),
    scopes=("attn/attn_window/attn_core", "attn/attn_full/attn_core",
            "attn/attn_gate", "rope/", "moe/moe_shared", "moe/moe_dispatch",
            "moe/moe_router", "moe_experts/", "moe_combine/", "cond/", "ffn"),
    float32_grad_rtol=2e-5,
    recomputed=((),), recomputed_rtol=(0.0, 1e-6))

# one period as the cell's: three Gated DeltaNet layers (2 key and 4 value
# heads of 16) and one gated attention layer (4 query heads on 2 key/value
# heads of 32, 8 features rotated); 16 experts of which numbers 4 to 7 are
# held, 3 a token, a gated shared expert; 128 positions, two chunks of the
# delta rule's 64; the routers trained, so that every leaf has a gradient to
# compare (the cell does not train them: the routers' case)
QWEN3_NEXT = Family(
    name="qwen3_next", cell="qwen3_next_80b_a3b.ssgd_longseq_1chip",
    module=qwen3_next,
    tiny=dict(hidden_size=64, head_dim=32, num_attention_heads=4,
              num_key_value_heads=2, linear_num_key_heads=2,
              linear_num_value_heads=4, linear_key_head_dim=16,
              linear_value_head_dim=16, num_experts=4, first_expert_held=4,
              num_experts_per_tok=3, moe_intermediate_size=32,
              shared_expert_intermediate_size=32,
              published={"num_experts": 16}, vocab_size=256, sequence_length=128,
              flash_blocks=[32, 32], flash_interpret=True,
              compute_dtype="float32", routers_trained=True),
    scales={"wq": 6.0, "wk": 6.0, "wv": 8.0, "w_qkvz": 4.0, "w_ba": 20.0,
            "router": 20.0, "w_shared_gate": 40.0, **_EXPERTS},
    norms=("ln1_scale", "ln2_scale", "q_norm_scale", "k_norm_scale",
           "gdn_norm_scale"),
    norm_key=lambda key, s, i: jax.random.fold_in(key, 8 * s + i),
    expert_layers=(0, 1, 2, 3), held_share=(0.1, 0.45),
    scopes=("gdn/", "gdn_proj/", "gdn_conv/", "gdn_core/", "gdn_norm/",
            "attn/attn_full/attn_core", "attn/attn_gate", "qk_norm/", "rope/",
            "moe/moe_shared", "moe/moe_dispatch", "moe/moe_router",
            "moe_experts/", "moe_combine/"),
    recomputed=((), (qwen3_next.LINEAR, qwen3_next.FULL)))

# the cell's stack in small: a dense layer and two expert layers, then the
# multi-token-prediction module; 4 heads of 24 unrotated + 8 rotated q/k
# features and 32 value features, latents of 24 and 16; 16 experts of which
# numbers 4 to 11 are held, 4 a token; 64 positions (66 ids); the routers
# trained, so that every leaf but the bias has a gradient to compare
GLM_4_7_FLASH = Family(
    name="glm_4_7_flash", cell="glm_4_7_flash.ssgd_mtp_8k_1chip",
    module=glm4_moe_lite,
    tiny=dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
              num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
              q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=24,
              qk_rope_head_dim=8, v_head_dim=32, n_routed_experts=8,
              first_expert_held=4, published={"n_routed_experts": 16},
              vocab_size=256, sequence_length=64, flash_blocks=[32, 32],
              flash_interpret=True, compute_dtype="float32",
              routers_trained=True),
    scales={"w_q_down": 6.0, "w_q_up": 6.0, "w_kv_down": 6.0, "w_kv_up": 6.0,
            "router": 20.0, "router_bias": 40.0, "w_gate": 8.0, "w_up": 8.0,
            "w_down": 8.0, "shared_gate": 3.0, "shared_up": 3.0,
            "shared_down": 3.0},
    norms=("ln1_scale", "ln2_scale", "q_latent_norm", "kv_latent_norm"),
    trained_more=_glm_module, expert_layers=(1, 2, 3), held_share=(0.3, 0.7),
    scopes=("attn/mla_down", "attn/mla_norm", "attn/mla_up", "attn/rope",
            "attn/attn_latent/attn_core", "moe/moe_router", "moe/moe_shared",
            "moe/moe_dispatch", "moe_experts/", "moe_combine/", "head_loss",
            "mtp_proj/"),
    constants=("router_bias",),
    recomputed=((), (glm4_moe_lite.DENSE, glm4_moe_lite.SPARSE)))

# the cell's stack in small, `M E M * E`: 8 Mamba-2 heads of 8 on 2 groups' B
# and C of 16; 4 query heads on 2 key/value heads of 16; 16 experts of which
# numbers 4 to 11 are held, 3 a token, a shared expert; 64 positions; the
# routers trained, so that every leaf but the bias has a gradient to compare
NEMOTRON_H = Family(
    name="nemotron_h", cell="nemotron_3_nano_30b_a3b.ssgd_ssm_8k_1chip",
    module=nemotron_h,
    tiny=dict(hidden_size=64, moe_intermediate_size=32,
              moe_shared_expert_intermediate_size=64, num_hidden_layers=5,
              hybrid_override_pattern="MEM*E", num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
              mamba_head_dim=8, ssm_state_size=16, n_groups=2,
              n_routed_experts=8, first_expert_held=4,
              published={"n_routed_experts": 16}, num_experts_per_tok=3,
              vocab_size=320, sequence_length=64, flash_blocks=[32, 32],
              flash_interpret=True, compute_dtype="float32",
              routers_trained=True),
    scales={"w_ssm_in": 4.0, "wq": 8.0, "wk": 8.0, "wv": 4.0, "router": 20.0,
            "router_bias": 40.0, "w_up": 8.0, "w_down": 8.0, "shared_up": 4.0,
            "shared_down": 4.0},
    norms=("ln1_scale", "ln2_scale", "ssm_norm_scale"),
    trained_more=_nemotron_memory, expert_layers=(1, 4), held_share=(0.3, 0.7),
    scopes=("ssm/ssm_proj/", "ssm/ssm_conv/", "ssm/ssm_core/", "ssm/ssm_norm/",
            "attn/attn_full/attn_core/", "moe/moe_router/", "moe/moe_shared/",
            "moe/moe_dispatch/", "moe_experts/", "moe_combine/", "head_loss"),
    constants=("router_bias",),
    recomputed=((), tuple(nemotron_h.LAYER_NAMES.values())))



def _ouro_gate(family, state, key):
    """A gate with preferences (g of a position some units from 0, off
    centre) and a head whose logits weigh, so that the exit shares differ a
    position and a loop step and the gate's gradient is a part of the
    whole."""
    return {**state, "exit_gate_w": 12.0 * state["exit_gate_w"],
            "exit_gate_b": state["exit_gate_b"] - 0.7,
            "lm_head": 4.0 * state["lm_head"]}


# the cell's model in small: two layers run four times, 4 heads of 16, a
# feed-forward of 96, 64 positions; the entropy's weight 40 times the cell's,
# so that its sign and the gate's gradient weigh in the whole gradient
OURO = Family(
    name="ouro", cell="ouro_2_6b.ssgd_loop4_4k_1chip", module=ouro,
    tiny=dict(hidden_size=64, intermediate_size=96, head_dim=16,
              num_attention_heads=4, num_key_value_heads=4,
              num_hidden_layers=2, vocab_size=256, sequence_length=64,
              exit_entropy_coef=2.0, flash_blocks=[32, 32],
              flash_interpret=True, compute_dtype="float32"),
    scales={"wq": 6.0, "wk": 6.0, "wv": 3.0},
    norms=("ln1_scale", "ln2_scale", "ln1_post_scale", "ln2_post_scale"),
    trained_more=_ouro_gate,
    scopes=("attn/attn_full/attn_core", "attn/post_norm", "ffn/post_norm",
            "rope/", "loop_norm", "head_loss", "exit_gate", "ffn"),
    recomputed=((), (ouro.FULL,)))



def _granite_boundaries(family, batch):
    """The drawn rows with ends of documents of the test's own choosing in
    the place of the drawn ones: in row 0 documents that end inside a scan
    chunk of the tests' 32 positions and inside a flash block (position 9,
    70), on a chunk's and a block's edge (31, so that position 32 is a
    document's first), one of a single position (32) and one that spans a
    whole chunk and more (71 to 127); row 1 two documents, the boundary at
    position 100."""
    end = granite_hybrid.end_of_document(family.config)
    batch = np.where(batch == end, 1, batch)
    batch[0, [9, 31, 32, 70]] = end
    batch[1, 99] = end
    return batch


# three layers as the cell's, Mamba-2, attention, Mamba-2, each with its
# gated feed-forward: 8 Mamba-2 heads of 16 on one group's B and C of 16; 4
# query heads on 2 key/value heads of 16 under the published scale 1/64; the
# four multipliers as published; a tied head; 128 packed positions, four
# chunks of the tests' scan (`GRANITE_CHUNK`)
GRANITE_HYBRID = Family(
    name="granite_hybrid", cell="granite_4_0_h_micro.ssgd_packed_1chip",
    module=granite_hybrid,
    tiny=dict(hidden_size=64, intermediate_size=96, shared_intermediate_size=96,
              num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"],
              num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
              mamba_d_head=16, mamba_d_state=16, vocab_size=320,
              sequence_length=128,
              documents=dict(distribution="lognormal", median=24, sigma=1.0,
                             shortest=4, longest=128, end_of_document_id=0),
              flash_blocks=[32, 32], flash_interpret=True,
              compute_dtype="float32"),
    scales={"w_ssm_in": 4.0, "wq": 40.0, "wk": 40.0, "wv": 8.0, "wo": 4.0,
            "w_gate": 4.0, "w_up": 4.0, "w_down": 4.0},
    norms=("ln1_scale", "ln2_scale", "ssm_norm_scale"),
    trained_more=_nemotron_memory, sampled=_granite_boundaries,
    scopes=("segments", "ssm/ssm_proj/", "ssm/ssm_conv/", "ssm/ssm_core/",
            "ssm/ssm_norm/", "attn/attn_full/attn_core/", "ffn", "embed",
            "head_loss"),
    recomputed=((), (granite_hybrid.MAMBA, granite_hybrid.ATTENTION)))
GRANITE_CHUNK = 32  # the scan's chunk in the family's tests (`ssm_scan.CHUNK`)

# every kind of the cell's layers once, `c a c` with the first feed-forward
# dense: 128 channels (a lane tile: the convolution's kernels run,
# interpreted), 3 taps; 4 query heads on 2 key/value heads of 32 behind a q/k
# norm a head and a rotary pass; 16 experts of which numbers 4 to 11 are held,
# 4 a token; 64 positions; the routers trained, so that every leaf but the
# bias has a gradient to compare
LFM2_MOE = Family(
    name="lfm2_moe", cell="lfm2_24b_a2b.ssgd_conv_8k_1chip", module=lfm2_moe,
    tiny=dict(hidden_size=128, intermediate_size=192, moe_intermediate_size=32,
              num_hidden_layers=3, num_dense_layers=1,
              layer_types=["conv", "full_attention", "conv"],
              num_attention_heads=4, num_key_value_heads=2,
              num_experts=8, first_expert_held=4, published={"num_experts": 16},
              vocab_size=320, sequence_length=64, flash_blocks=[32, 32],
              flash_interpret=True, compute_dtype="float32",
              routers_trained=True),
    scales={"conv_in": 5.0, "conv_w": 20.0, "conv_out": 3.0, "wv": 14.0, "wo": 8.0,
            "router": 20.0, "router_bias": 120.0, "w_gate": 8.0, "w_up": 8.0,
            "w_down": 8.0},
    norms=("ln1_scale", "ln2_scale", "q_norm_scale", "k_norm_scale"),
    expert_layers=(1, 2), held_share=(0.3, 0.7),
    scopes=("sconv/sconv_proj/", "sconv/sconv_core/", "attn/attn_full/attn_core",
            "qk_norm/", "rope/", "ffn", "moe/moe_router", "moe/moe_dispatch",
            "moe_experts/", "moe_combine/", "embed", "head_loss"),
    constants=("router_bias",),
    recomputed=((), (lfm2_moe.DENSE, lfm2_moe.SPARSE)))

# two of the cell's layers: 4 query heads on 2 key/value heads of 16 behind a
# q/k norm a head and a rotary pass, an indexer of 2 heads of 8 that picks 16
# keys a query of 64 positions (three rows in four choose), the plain forms of
# `ops.sparse_attention` (`attention_core` dense; the kernels, interpreted, are
# a case of the family's file); 8 experts of which numbers 2 to 5 are held,
# 3 a token; the routers trained, so that every leaf has a gradient to compare;
# the indexer's loss at a weight of 1 and the three groups of the comparison
# weighed alike at this size; the reference in four blocks of rows
KEYE_VL2 = Family(
    name="keye_vl2", cell="keye_vl_2_0_30b_a3b.ssgd_dsa_1chip", module=keye_vl2,
    tiny=dict(hidden_size=64, moe_intermediate_size=32, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              sa_config=dict(indexer_head_dim=8, indexer_num_heads=2,
                             indexer_num_kv_heads=1, kv_chunk_size=32,
                             q_chunk_size=32, topk=16),
              num_experts=4, num_local_experts=4, first_expert_held=2,
              published={"num_experts": 8}, num_experts_per_tok=3,
              vocab_size=320, sequence_length=64, flash_blocks=[32, 32],
              flash_interpret=True, attention_core="dense",
              compute_dtype="float32", routers_trained=True,
              indexer_loss_weight=1.0,
              compared_weights=dict(indexer_grads=0.4, indexer_kl=0.4),
              reference_row_block=16, reference_position_block=16),
    scales={"wq": 6.0, "wk": 6.0, "wv": 8.0, "wo": 3.0, "index_wq": 8.0,
            "index_wk": 8.0, "index_w": 30.0, "router": 20.0, "w_gate": 8.0,
            "w_up": 8.0, "w_down": 8.0},
    norms=("ln1_scale", "ln2_scale", "q_norm_scale", "k_norm_scale",
           "index_ln_scale", "index_ln_bias"),
    expert_layers=(0, 1), held_share=(0.3, 0.7),
    scopes=("attn/attn_proj/", "qk_norm/", "rope/", "attn/dsa_index/",
            "attn/dsa_select/", "attn/attn_sparse/attn_core/", "attn/dsa_kl/",
            "moe/moe_router", "moe/moe_dispatch", "moe_experts/", "moe_combine/",
            "embed", "head_loss"),
    recomputed=((),))  # the cell's layers are run again: against none that are

# two periods of the cell's pattern, so that a full layer follows window
# layers: a full-attention layer without positions and three window-16 layers
# with rotary positions, twice (four stacks); 4 query heads on 2 key/value heads
# of 16; 8 relu-gated experts of which numbers 2 to 5 are held, 3 a token,
# routed from the layer's input; 64 positions in blocks of 16, so that a band
# is two blocks wide; the routers trained, so that every leaf has a gradient
# to compare; the reference in four blocks of rows
SMALLTHINKER = Family(
    name="smallthinker", cell="smallthinker_21b_a3b.ssgd_swa_nope_1chip",
    module=smallthinker,
    tiny=dict(hidden_size=64, moe_ffn_hidden_size=32, num_hidden_layers=8,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              sliding_window_size=16, rope_layout=[0, 1, 1, 1] * 2,
              sliding_window_layout=[0, 1, 1, 1] * 2,
              moe_num_primary_experts=4, first_expert_held=2,
              published={"moe_num_primary_experts": 8},
              moe_num_active_primary_experts=3, vocab_size=320,
              sequence_length=64, flash_blocks=[16, 16], flash_interpret=True,
              compute_dtype="float32", routers_trained=True,
              reference_query_block=16, reference_position_block=16),
    scales={"wq": 6.0, "wk": 6.0, "wv": 8.0, "wo": 3.0, "router": 20.0,
            "w_gate": 8.0, "w_up": 8.0, "w_down": 8.0},
    norms=("ln1_scale", "ln2_scale"),
    expert_layers=tuple(range(8)), held_share=(0.3, 0.7),
    scopes=("moe/moe_early_router/", "moe/moe_plan/", "attn/attn_window/attn_core",
            "attn/attn_full/attn_core", "rope/", "moe/moe_router",
            "moe_dispatch/", "moe_experts/", "moe_combine/", "embed",
            "head_loss"),
    recomputed=((),))  # the cell's layers are run again: against none that are

# one period of it, for the faults' file and the family's own cases: every
# fault is a program of its own, and four layers are two stacks for four
SMALLTHINKER_ONE_PERIOD = dataclasses.replace(SMALLTHINKER, tiny={
    **SMALLTHINKER.tiny, "num_hidden_layers": 4, "rope_layout": [0, 1, 1, 1],
    "sliding_window_layout": [0, 1, 1, 1]}, expert_layers=tuple(range(4)))



def _kimi_memory(family, state, key):
    """In a KDA layer decays that weigh and differ feature by feature: A in
    [0.1, 0.6] a head under steps softplus(f + dt_bias) with dt_bias in
    [-2, 2] a feature, a log decay of -0.01 to -1.3 a position, in the place
    of the start's few thousandths."""
    def remembering(stack, key):
        if "A_log" not in stack:
            return stack
        return {**stack,
                "A_log": jnp.log(jax.random.uniform(
                    jax.random.fold_in(key, 7), stack["A_log"].shape,
                    minval=0.1, maxval=0.6)),
                "dt_bias": jax.random.uniform(
                    jax.random.fold_in(key, 8), stack["dt_bias"].shape,
                    minval=-2.0, maxval=2.0)}

    return {**state, "layers": tuple(
        remembering(stack, jax.random.fold_in(key, 10 + s))
        for s, stack in enumerate(state["layers"]))}


# the cell's first four layers in small, so that the one period's latent layer
# is there: KDA with the dense feed-forward, two KDA expert layers and the
# latent expert layer (three stacks); 4 heads of 16 for KDA's q, k and v; 4
# latent heads of 16 + 8 q/k features on 8 value features, a latent of 16,
# no q latent, nothing turned, a sharp softmax so that its scale weighs; 16 experts of which numbers 4 to 11 are held,
# 4 a token; 128 positions, two chunks of the rule's 64; the routers trained,
# so that every leaf but the bias has a gradient to compare
KIMI_LINEAR = Family(
    name="kimi_linear", cell="kimi_linear_48b_a3b.ssgd_kda_1chip",
    module=kimi_linear,
    tiny=dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
              num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
              kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
              v_head_dim=8, num_experts=8, first_expert_held=4,
              num_experts_per_token=4, published={"num_experts": 16},
              vocab_size=256, sequence_length=128, flash_blocks=[32, 32],
              flash_interpret=True, compute_dtype="float32",
              routers_trained=True, reference_query_block=32,
              reference_position_block=32),
    configured=lambda config: config["linear_attn_config"].update(
        num_heads=4, head_dim=16),
    scales={"w_q": 4.0, "w_k": 4.0, "w_v": 4.0, "w_f_a": 6.0, "w_f_b": 6.0,
            "w_beta": 20.0, "w_g_a": 6.0, "w_g_b": 6.0, "w_q_up": 20.0,
            "w_kv_down": 6.0, "w_kv_up": 20.0, "wo": 3.0, "router": 20.0,
            "router_bias": 40.0, "w_gate": 8.0, "w_up": 8.0, "w_down": 8.0,
            "shared_gate": 3.0, "shared_up": 3.0, "shared_down": 3.0},
    norms=("ln1_scale", "ln2_scale", "kv_latent_norm", "kda_norm_scale"),
    trained_more=_kimi_memory, expert_layers=(1, 2, 3), held_share=(0.3, 0.7),
    scopes=("kda/", "kda_proj/", "kda_conv/", "kda_core/", "kda_norm/",
            "attn/mla_down", "attn/mla_norm", "attn/mla_up",
            "attn/attn_latent/attn_core", "moe/moe_router", "moe/moe_shared",
            "moe/moe_dispatch", "moe_experts/", "moe_combine/", "head_loss"),
    constants=("router_bias",),
    recomputed=((),))  # the cell's layers are run again: against none that are



def _xing_maps(family, state, key):
    """Maps in which the static and the dynamic part both weigh: gains that
    differ a map (the start's are one number), and logits of the streams'
    own map far apart and on no diagonal (7 x normal in the place of the
    start's 2 I + normal), so that one Sinkhorn pass, or a softmax over the
    rows, is far from twenty passes; the module as `_glm_module` leaves it,
    its block's maps as a layer's."""
    def mapped(layer, at):
        layer = {name: leaf * jnp.asarray([3.0, 2.0, 6.0]) if name.endswith("_a")
                 else leaf for name, leaf in layer.items()}
        for i, name in enumerate(("hc1_b", "hc2_b")):
            own = 7.0 * jax.random.normal(
                jax.random.fold_in(key, 40 + 2 * at + i), layer[name][..., 8:].shape)
            layer[name] = layer[name].at[..., 8:].set(own)
        return layer

    state = {**state, "layers": tuple(mapped(stack, s)
                                      for s, stack in enumerate(state["layers"]))}
    if "mtp" not in state:
        return state
    state = _glm_module(family, state, key)
    return {**state, "mtp": {**state["mtp"],
                             "layer": mapped(state["mtp"]["layer"], 10)}}


# the cell's stack in small with the module ON (the cell leaves it with a
# later stage): a dense layer and an expert layer, then the module's expert
# block, each branch under maps of its own over 4 streams of 64 features, 20
# Sinkhorn passes; 4 heads of 24 unrotated + 8 rotated q/k features on 16
# value features, latents of 24 and 16, YaRN by 8 over 64 original positions,
# so that of the 4 pairs one keeps its frequency, one blends and two take
# theirs over 8, and the softmax's scale is 1.46 of its own;
# 16 experts of which numbers 4 to 11 are held, 4 a token; the routers
# trained, so that every leaf but the bias has a gradient to compare
XING4_0 = Family(
    name="xing4_0", cell="xing4_0_29b_a4b.ssgd_mhc_4k_1chip", module=xing4_0,
    tiny=dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
              num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
              q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=24,
              qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
              first_expert_held=4, published={"n_routed_experts": 16},
              num_nextn_predict_layers=1, vocab_size=256, sequence_length=64,
              flash_blocks=[32, 32], flash_interpret=True,
              compute_dtype="float32", routers_trained=True),
    configured=lambda config: config["rope_scaling"].update(
        original_max_position_embeddings=64, factor=8),
    scales={"w_q_down": 6.0, "w_q_up": 6.0, "w_kv_down": 6.0, "w_kv_up": 6.0,
            "router": 20.0, "router_bias": 40.0, "w_gate": 8.0, "w_up": 8.0,
            "w_down": 8.0, "shared_gate": 3.0, "shared_up": 3.0,
            "shared_down": 3.0, "hc1_phi": 8.0, "hc2_phi": 8.0},
    norms=("ln1_scale", "ln2_scale", "q_latent_norm", "kv_latent_norm"),
    trained_more=_xing_maps, expert_layers=(1, 2), held_share=(0.3, 0.7),
    scopes=("hc/hc_maps", "hc/hc_read", "hc/hc_write", "hc_in", "hc_out",
            "attn/mla_down", "attn/mla_norm", "attn/mla_up", "attn/rope",
            "attn/attn_latent/attn_core", "moe/moe_router", "moe/moe_shared",
            "moe/moe_dispatch", "moe_experts/", "moe_combine/", "ffn",
            "head_loss", "mtp_proj/"),
    constants=("router_bias",),
    recomputed=((),))  # the cell's layers are run again: against none that are

# the two layers without the module, for the faults' file: every fault is a
# program of its own, and the module's block is a third of one
XING4_0_STACK = dataclasses.replace(XING4_0, tiny={
    **XING4_0.tiny, "num_nextn_predict_layers": 0}, expert_layers=(1,))

FAMILIES = (LAGUNA, QWEN3_NEXT, GLM_4_7_FLASH, NEMOTRON_H, OURO,
            GRANITE_HYBRID, LFM2_MOE, KEYE_VL2, SMALLTHINKER, KIMI_LINEAR,
            XING4_0)


# what the held experts get of a family's choices, as the number its routers'
# weights of the steering feature are moved by (`steered`)
HELD_LOADS = {"balanced": 0.0, "nothing": -100.0, "every_choice": 100.0}


def steered(family, toward: float):
    """`family.state()` with routers that send the held experts every choice
    (`toward` > 0) or none (< 0) whatever the token: every embedding row's
    first feature is 4, a number the residual stream keeps positive through
    the layers, its norm's scale 1, and the held experts' router weights of
    that feature are moved by `toward`. The state itself at 0."""
    state = family.state()
    if not toward:
        return state
    first, held = family.module.model_config(family.config).experts_held

    def steer(stack):
        return {**stack, "ln2_scale": stack["ln2_scale"].at[:, 0].set(1.0),
                "router": stack["router"].at[:, 0, first:first + held].add(toward)}

    layers = state["layers"]
    return {**state, "embed": state["embed"].at[:, 0].set(4.0),
            "layers": (steer(layers) if isinstance(layers, dict)
                       else tuple(steer(stack) for stack in layers))}


def held_load_is_the_references(family, load: str):
    """The float32 program against the reference, loss and gradients (the
    logits through the loss) in the tolerance of
    `test_float32_program_equals_the_reference`, with the held experts
    getting `load` of `HELD_LOADS` in every expert layer; and the share's
    chunks: without a selection bias half of all that can fall here at these
    sizes (PR 68: never more, so that the rows moved follow the rows that
    came), as many as the held rows fill, with the groups that came (a
    share's work is its live rows', PR 66). So a layer that holds more than
    half of what can fall here, every choice among them, runs two chunks
    against the reference, and one that holds nothing none."""
    module, config = family.module, family.config
    state, sample = steered(family, HELD_LOADS[load]), family.sample()
    loss, grads = family.program()(state, sample)
    want_loss, want = module.reference_loss_and_grads(config, state, sample)
    assert off(loss, want_loss) <= 1e-5
    assert harness.relative_error(grads, want) <= family.float32_grad_rtol
    mc = module.model_config(config)
    assert not mc.router_bias
    tokens = sample[:, :-1].size
    most = tokens * min(mc.top_k, mc.experts_held[1])
    half = -(-most // 16) * 8
    assert moe._share_chunk(tokens, mc.top_k, mc.experts_held[1], mc.n_experts,
                            mc.router_bias) == half < most
    stats = module.routing_stats(config, state, sample)
    rows = {"nothing": [0], "every_choice": [most]}.get(load) or range(1, most)
    assert all(n in rows for n in stats["held_rows"]), stats["held_rows"]
    assert stats["chunk_rows"] == [-(-n // half) * half
                                   for n in stats["held_rows"]]
    if load == "every_choice":
        assert stats["chunk_rows"] == [2 * half] * len(family.expert_layers)
    assert stats["dropped"] == [0] * len(family.expert_layers)


def pytest_generate_tests(metafunc):
    """A family's file takes this as its own: every shared case for its
    `FAMILY`, under its name, a case a list of `recomputed` and a fault."""
    family = metafunc.module.FAMILY
    if "family" in metafunc.fixturenames:
        metafunc.parametrize("family", [family], ids=[family.name])
    if "recomputed" in metafunc.fixturenames:
        metafunc.parametrize("recomputed", family.recomputed,
                             ids=lambda kinds: "+".join(kinds) or "none")
    if "fault" in metafunc.fixturenames:
        metafunc.parametrize("fault", sorted(
            {"eight_bit_operands", *family.faults, *family.state_faults}))


def off(got, want) -> float:
    return abs(float(got) - float(want)) / abs(float(want))


def test_float32_program_equals_the_reference(family):
    loss, grads = family.baseline()
    want_loss, want = family.reference()
    assert off(loss, want_loss) <= 1e-5
    assert harness.relative_error(grads, want) <= family.float32_grad_rtol
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        if any(constant in name for constant in family.constants):  # in both
            assert not np.asarray(g).any() and not np.asarray(w).any(), name
            continue
        assert float(jnp.abs(g).max()) > 0, name
        assert harness.relative_error(g, w) <= 1e-3, name
    if family.expert_layers:
        assert family.module.differing_choices(
            family.config, family.state(), family.sample()) == 0


def test_bfloat16_program_is_within_the_familys_tolerances(family):
    module = family.module
    config = family.tiny_config(compute_dtype="bfloat16")
    state, sample = module.init(config, family.seed), family.sample()
    loss, grads = module.program_loss_and_grads(config)(state, sample)
    want_loss, want = module.reference_loss_and_grads(family.config, state, sample)
    assert off(loss, want_loss) <= module.LOSS_RTOL
    error = harness.relative_error(grads, want)
    assert 1e-4 < error <= module.GRAD_RTOL, error
    assert 0 < module.LOSS_RTOL < module.GRAD_RTOL < 0.1


def test_the_recomputed_layers_change_no_number(family, recomputed):
    """`recomputed_layer_types` says what the backward pass keeps, not what
    it computes, in the stacks and in a module's block alike."""
    mc = family.module.model_config(
        family.tiny_config(recomputed_layer_types=list(recomputed)))
    kinds = [kind for kind, _ in mc.stacks] + ([mc.mtp_kind] if mc.mtp_depth else [])
    assert {kind.layer_remat for kind in kinds} == {bool(recomputed)}
    loss, grads = family.baseline()
    want_loss, want = family.baseline(recomputed)
    loss_rtol, grad_rtol = family.recomputed_rtol
    assert off(loss, want_loss) <= loss_rtol
    assert harness.relative_error(grads, want) <= grad_rtol


def test_param_pspecs_cover_every_leaf(family):
    """The sharding plan names every leaf, stack by stack."""
    state = jax.eval_shape(lambda: family.module.init(family.config, 0))
    specs = param_pspecs(family.module.model_config(family.config))
    is_spec = lambda s: isinstance(s, PartitionSpec)
    assert jax.tree.structure(jax.tree.map(lambda s: 0, specs, is_leaf=is_spec)
                              ) == jax.tree.structure(jax.tree.map(lambda s: 0, state))
    for spec, leaf in zip(jax.tree.leaves(specs, is_leaf=is_spec),
                          jax.tree.leaves(state)):
        assert len(spec) <= leaf.ndim, (spec, leaf.shape)
    family.named_specs(specs)


def test_a_tp_mesh_of_two_gives_the_same_loss(family):
    from kungfu_tpu.parallel import make_mesh
    from kungfu_tpu.parallel.sharded import shard_params

    module, config = family.module, family.config
    mesh = make_mesh({"dp": 1, "tp": 2, "ep": 1}, devices=jax.devices()[:2])
    placed = shard_params(family.state(), mesh,
                          param_pspecs(module.model_config(config)))
    leaf = functools.reduce(lambda tree, at: tree[at], family.tp_leaf, placed)
    assert len(leaf.sharding.device_set) == 2
    with mesh:
        got = float(jax.jit(module.loss_fn(config))(placed, family.sample()))
    assert got == pytest.approx(float(family.baseline()[0]), rel=1e-5)


def test_the_scopes_the_cells_metrics_read_are_in_the_program(family):
    for scope in family.scopes:
        assert scope in family.lowered(), scope


def test_the_share_drops_nothing_and_counts_its_rows(family):
    """The program's routing counters on the trained-like state: a row an
    expert layer (a module's the last), nothing dropped, the held experts'
    rows about their share of the choices, under a selection bias some moved
    and not all; and their gauges, a series a layer that routes and no other."""
    module, config = family.module, family.config
    state, sample = family.state(), family.sample()
    mc = module.model_config(config)
    if not family.expert_layers:  # no router, and the program says so
        with pytest.raises(ValueError, match="no expert layer"):
            transformer.routing_stats(state, sample[:, :-1], mc)
        return
    layers, held = list(family.expert_layers), mc.experts_held[1]
    tokens = 2 * config["sequence_length"]
    stats = module.routing_stats(config, state, sample)
    assert stats["dropped"] == [0] * len(layers) and stats["layer"] == layers
    counts = np.asarray(stats["counts"])
    assert counts.shape == (len(layers), held)
    assert stats["held_rows"] == counts.sum(axis=1).tolist()
    low, high = family.held_share
    assert low < counts.sum() / (len(layers) * tokens * mc.top_k) < high
    gauges = ["dropped_token_choices", "held_rows", "held_share",
              "max_over_mean_load"]
    if mc.router_bias:
        gauges.append("bias_moved_token_choices")
        assert len(stats["bias_moved"]) == len(layers)
        assert all(0 < n < tokens * mc.top_k for n in stats["bias_moved"])
    full = jax.jit(lambda p, t: transformer.routing_stats(p, t, mc))(
        state, sample[:, :-1])
    assert full["chosen"].shape == (len(layers), tokens, mc.top_k)
    registry = metrics.Registry()
    transformer.record_routing(full, registry)
    text = registry.render()
    for layer in range(max(layers) + 1):
        if layer not in layers:
            assert f'layer="{layer}"' not in text
            continue
        for gauge in gauges:
            assert f'kungfu_moe_{gauge}{{layer="{layer}"}}' in text
        assert f'kungfu_moe_dropped_token_choices{{layer="{layer}"}} 0' in text
        assert f'kungfu_moe_held_share{{layer="{layer}"}} 0.' in text
        assert (f'kungfu_moe_expert_token_choices{{layer="{layer}",'
                f'expert="{held - 1}"}}') in text


def test_routers_that_are_not_trained_get_no_gradient_and_change_no_other(family):
    """The cell's own setting: the routers' matrices are constants of the
    loss, in the program and in the reference alike, a module's among them;
    every other leaf's gradient is what it is with the routers trained. A
    family without the setting trains its routers."""
    def routed(tree):  # the stacks, and a module's block, that hold a router
        holds = lambda node: isinstance(node, dict) and "router" in node
        return [node for node in jax.tree.leaves(tree, is_leaf=holds) if holds(node)]

    trained_loss, trained = family.reference()
    if "routers_trained" not in family.config:
        assert all(np.asarray(layer["router"]).any() for layer in routed(trained))
        return
    config = family.tiny_config(routers_trained=False)
    assert mf.cell(mf.load(), family.cell)["config"]["routers_trained"] is False
    state, sample = family.state(), family.sample()
    loss, grads = family.module.program_loss_and_grads(config)(state, sample)
    want_loss, want = family.module.reference_loss_and_grads(config, state, sample)
    assert float(want_loss) == float(trained_loss)
    assert off(loss, want_loss) <= 1e-5
    assert harness.relative_error(grads, want) <= 1e-4
    assert routed(grads)
    for got, reference, full in zip(*map(routed, (grads, want, trained)),
                                    strict=True):
        assert not np.asarray(got["router"]).any()
        assert not np.asarray(reference["router"]).any()
        assert np.asarray(full["router"]).any()
        for name in reference:
            if name != "router":
                np.testing.assert_array_equal(reference[name], full[name])


def shares_add_up(n, w, cfg, held, want, chosen, shared, routed_from=None):
    """Model-configs guide, section 4: one expert layer of `cfg` on rows n
    (T, D) with weights w, cut into shares of `held` experts. Each share
    routes over all the experts and computes its own experts' part and the
    shared expert, which every chip computes alike; the parts of all, the
    shared expert's `shared` counted once, are the uncut reference's `want`
    for the whole layer, and their counts those of its choices `chosen`.
    `routed_from`: the rows (T, D) a layer that routes ahead of its mixer
    reads in the place of n (`router_input` "layer")."""
    choices = cfg.top_k * n.shape[0]

    def share(first):
        mine = {name: leaf[first:first + held] if leaf.ndim == 3 else leaf
                for name, leaf in w.items()}
        own = dataclasses.replace(cfg, experts_held=(first, held))
        routing = None if routed_from is None else transformer._early_routing(
            routed_from[None], mine, own)
        return transformer._expert_layer(n, mine, own, routing)

    parts = [share(first) for first in range(0, cfg.n_experts, held)]
    total = sum(y for y, _ in parts) - (len(parts) - 1) * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    counts = np.concatenate([np.asarray(aux.counts) for _, aux in parts])
    assert counts.tolist() == np.bincount(np.asarray(chosen).ravel(),
                                          minlength=cfg.n_experts).tolist()
    assert counts.sum() == choices
    if cfg.router_bias:  # every share sees the same router
        moved = {int(aux.bias_moved) for _, aux in parts}
        assert len(moved) == 1 and 0 < moved.pop() < choices
    # one share alone is not the layer: the cut is real
    assert not np.allclose(np.asarray(parts[0][0]), np.asarray(want), atol=1e-2)
    return len(parts)


def refused(match, **fields):
    """A `TransformerConfig` of these fields is a ValueError that says so."""
    with pytest.raises(ValueError, match=match):
        transformer.TransformerConfig(**fields)


def model_changed(module, **changes):
    """The fault that replaces fields of the family's model configuration:
    in every layer kind that sets them, else in the configuration's own."""
    model_config = module.model_config

    def changed(cfg):
        mc = model_config(cfg)
        kinds = tuple(tuple((k, changes.get(k, v)) for k, v in kind)
                      for kind in mc.layer_kinds)
        own = {k: v for k, v in changes.items()
               if not any(k == name for kind in kinds for name, _ in kind)}
        return dataclasses.replace(mc, layer_kinds=kinds, **own)

    return lambda m: m.setattr(module, "model_config", changed)


def bias_in_the_weight(m):
    """The fault of a router whose weights are the biased scores."""
    def route(x, router_w, top_k, scores="softmax", bias=None):
        logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
        biased = jax.nn.sigmoid(logits) + bias
        top, idx = jax.lax.top_k(biased, top_k)
        return logits, biased, top, idx

    m.setattr(moe, "route", route)


def gate_after_the_norm(m):
    """The fault of a Mamba-2 mixer whose gated norm takes the mean square
    before the gate: rms_G(o + d x) * scale * silu(z) in the place of
    `ops.gated_norm.gated_norm`."""
    m.setattr(gated_norm, "gated_norm", _norm_then_gate)


def _norm_then_gate(o, x, z, d, scale, groups, eps):
    B, H, S, P = o.shape
    x, z = (t[..., :H * P].astype(jnp.float32) for t in (x, z))
    y = (o.transpose(0, 2, 1, 3).astype(jnp.float32)
         + d[:, None] * x.reshape(B, S, H, P)).reshape(B, S, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
    return y.reshape(z.shape) * scale * jax.nn.silu(z)


def norm_over(groups: int):
    """The fault of a Mamba-2 mixer whose gated norm takes the mean square
    over `groups` groups of features whatever the configuration says."""
    def fault(m):
        norm = gated_norm.gated_norm
        m.setattr(gated_norm, "gated_norm", lambda o, x, z, d, scale, _, eps:
                  norm(o, x, z, d, scale, groups, eps))

    return fault


def _eight_bit(state):
    """Every matrix rounded to float8_e4m3: 8-bit operands in the matmuls."""
    return jax.tree.map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype) if w.ndim >= 2 else w,
        state)


def test_a_fault_fails_the_familys_tolerance(family, fault, monkeypatch,
                                             fresh_traces):
    """Each in float32 compute, so that nothing but the fault is in the
    error: it has to be far over what the bfloat16 program is allowed. A
    fault patches the program, or changes the state it is given
    (`state_faults`, and every family's eight-bit operands)."""
    state, sample = family.state(), family.sample()
    _, want = family.reference()
    family.faults.get(fault, lambda m: None)(monkeypatch)
    changed = {"eight_bit_operands": _eight_bit, **family.state_faults}.get(
        fault, lambda state: state)
    _, grads = family.module.program_loss_and_grads(family.config)(
        changed(state), sample)
    error = harness.relative_error(grads, want)
    assert error > 2 * family.module.GRAD_RTOL, (fault, error)


# a faults' file's case, and a family's own file's: `from family_cases import *`
FAULT_CASE = "test_a_fault_fails_the_familys_tolerance"
CASES = tuple(name for name in dict(globals())
              if name.startswith("test_") and name != FAULT_CASE)
__all__ = ["pytest_generate_tests", *CASES]
