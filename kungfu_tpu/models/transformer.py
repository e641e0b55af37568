"""Flagship decoder-only transformer LM with an explicit sharding plan.

TPU-first design notes:
- Params live in a plain pytree with a parallel tree of PartitionSpecs
  (param_pspecs): Megatron-style tensor parallelism over the 'tp' mesh
  axis (column-parallel QKV/FF-in, row-parallel O/FF-out), batch over
  'dp', optional sequence sharding over 'sp' for activations. XLA's SPMD
  partitioner inserts the AllReduce/AllGather collectives over ICI from
  these annotations — nothing is hand-scheduled.
- Compute in bfloat16 (MXU native), params and optimizer state in f32.
- Static shapes everywhere; layers are stacked and scanned-friendly.
- The layer is data (ROADMAP D1): `TransformerConfig` says where positions
  come from (a learned table or rotary), whether q and k are normed, what
  the feed-forward is (gelu, gated silu, or routed experts through
  `ops.moe.moe_ffn`), whether the head is tied, and which attention core
  runs (XLA's dense one or `ops.flash_attention`). The defaults are the
  block the repo has always had, so `bert_base()` and `tiny()` mean what
  they meant; `olmoe_1b_7b()` is the first published architecture.
- Layers may differ in kind (PR 33): `layer_kinds` gives each layer the
  fields that replace the configuration's own for it (heads, window, rotary
  rule, feed-forward), successive layers of one kind are one stacked tree
  and one `lax.scan`, and `params["layers"]` is then the tuple of those
  stacks in the model's layer order. Grouped heads with a head size of
  their own, a band mask, a per-head output gate, rotary over part of the
  head with YaRN's frequencies, renormalised and scaled expert gates, a
  shared expert, and an expert layer that holds a share of the experts its
  router sees are each a field.
- A layer's token mixer is a kind too (PR 36): `mixer` is softmax attention
  or the gated delta rule (`ops.gated_delta`: a fused q, k, v, z projection,
  a causal depthwise convolution, a linear recurrence with a matrix state a
  head, a gated norm), and layers of both mixers stand in one stack of
  `layer_kinds`. Norms with the scale 1 + w, q/k norms a head, a gate a
  feature from a q projection of twice the width and a sigmoid gate on the
  shared expert are each a field.
- Latent attention is the third mixer (PR 41, DeepSeek-V2's MLA as
  GLM-4.7-Flash has it): q through a normed latent, keys and values through
  another, one rotary key shared by every head beside each head's unrotated
  features (`latent_dims`, `_latent_attention`); k and v are laid out a head
  for the core every other layer runs. The router's scores are a softmax
  over the experts or a sigmoid an expert (`router_scores`), and a selection
  bias an expert may move the choice and never the weight (`router_bias`,
  `ops.moe.route`). A multi-token-prediction module (`mtp_depth` 1: two
  norms, a (2D, D) projection, one further block, a final norm of its own)
  predicts the token after the next on the shared embedding and head, and
  `transformer_loss` is then main loss + `mtp_weight` x MTP loss from a
  batch of S + 2 ids.
- A layer may be one residual branch alone (PR 43, Nemotron-H's): `mixer`
  "none" is a layer that is a feed-forward behind its one norm, `ffn`
  "none" a layer that is a mixer behind its one, and such a layer has that
  branch's norm leaf and no other. The Mamba-2 mixer is the fourth
  (`mixer="mamba2"`, `ssm_dims`, `_mamba2_mixer`): one fused projection to
  [z | x B C | dt], a causal depthwise convolution with a bias, a selective
  step Delta = softplus(dt + dt_bias) and the diagonal state-space
  recurrence H_t = exp(Delta_t A) H_{t-1} + Delta_t x_t B_t^T, y_t = H_t C_t
  + D x_t (`ops.ssm_scan`, the chunked scan's second rule), a norm over
  groups of features behind the gate silu(z), W_out. `positions` "none" adds
  no position signal anywhere, and `expert_act` "relu2" makes the routed
  experts and the shared expert two matrices, W_down (relu(W_up x))^2.
- The stacks may be run more than once a forward pass (PR 48, Ouro's looped
  model): `loop_steps` T > 1 makes the layer scans the body of an outer scan
  of T iterations over the one set of weights, the model's final norm at the
  end of every loop step and the normed state what the next one reads
  (`_hidden`, scope `loop_norm`); a shared leaf's gradient is the sum over
  its T uses, so under S-SGD on several chips the stacks are averaged whole
  and once, after the backward pass, and not a layer an iteration. The loss
  is then the expected cross-entropy over T head passes on the shared head
  under an exit distribution from a gate on the normed states
  (`exit_gate_w`, `exit_gate_b`), less `exit_entropy_coef` times that
  distribution's entropy (`_loop_losses`, scope `exit_gate`); each head
  pass is run again in the backward pass (`_loop_step_rows`), and
  `transformer_apply` gives the last loop step's logits. `post_norms` puts
  a second RMSNorm behind each branch of a layer, on the branch's output
  (`ln1_post_scale`, `ln2_post_scale`, scope `post_norm`).
- Four multipliers (PR 52, Granite 4.0's): the embedding's rows times
  `embedding_multiplier`, a branch's output times `residual_multiplier`
  where the residual takes it, the attention scores times
  `attention_multiplier` in the place of 1 / sqrt(head size), the logits over
  `logits_scaling`; each 1 (or unset) leaves the program as it was. A Mamba-2
  mixer may have a feed-forward behind it in one layer, and a tied head may
  stand behind a state-space stack.
- A row may be several documents (PR 52): `end_of_document` names the id that
  ends one, and the ids are the only carrier. `_segments` numbers each
  position's document (scope `segments`), and the numbers go to every mixer
  of the stack, through the layer scans and `_layer_again` alike: a
  convolution tap does not reach into an earlier document
  (`ops.gated_delta.causal_conv`; `ops.ssm_conv`, whose kernels read each
  position's depth into its document, made beside the numbers once a step,
  `ops.ssm_conv.document_marks`), the scan's state is zero before a
  document's first position (`ops.ssm_scan`), and a query sees the keys of
  its own document (`ops.flash_attention`). The loss is over every position.
  `packing_stats` says what a batch is made of. Without the id every program
  is what it was.
- The gated short convolution is the fifth mixer (PR 57, LFM2's operator):
  `mixer` "short_conv" is a layer whose mixer is [B | C | x] = h W_in (three
  equal thirds of 3 D columns), a causal depthwise convolution of `conv_taps`
  taps over B * x, the gate C on its output and W_out (`_short_conv_mixer`,
  `ops.short_conv`: the two gates and the taps in one kernel each way): no
  recurrence, no softmax, no activation, no bias, no norm of its own and no
  state in training. Its leaves are `conv_in`, `conv_w` and `conv_out`; packed
  rows' segments go to it as to every mixer. Layers of it stand in one stack
  of `layer_kinds` beside attention, dense and expert layers (`lfm2_24b_a2b`).
- Learned sparse attention (PR 61, DeepSeek Sparse Attention on grouped heads,
  `keye_vl_2_0_30b_a3b`): `sparse_index` = (indexer heads, indexer head size,
  keys a query) gives an attention layer a lightning indexer (leaves
  `index_wq`, `index_wk`, `index_w`, `index_ln_scale`, `index_ln_bias`) on the
  layer's normed input with its gradient stopped, the choice of each query's
  best-scored keys at or before it, the softmax core over the chosen keys
  alone and the indexer's own loss, the KL divergence of its distribution from
  the head-mean of the core's probabilities (`_sparse_attention`,
  `ops.sparse_attention`). The cross-entropy reaches no leaf of the indexer
  and the indexer's loss no other leaf; the layers' sum of it, times
  `indexer_loss_weight`, is in `transformer_loss` beside the routers' losses
  (`LayerAux`). `()` is every earlier configuration's program, text for text.

The reference has no model code (KungFu is model-agnostic); this model is
the framework's flagship workload for the BERT-config benchmark
(BASELINE.md config 3) and the long-context/sequence-parallel path.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from kungfu_tpu.ops import collective


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq: int = 512
    dtype: Any = jnp.bfloat16
    # what the layer is; every default is the repo's own block
    # "learned", "rope" (rotate-half over the whole head) or "none": no
    # position signal of any kind
    positions: str = "learned"
    rope_theta: float = 10000.0
    qk_norm: bool = False  # RMSNorm over all of q and of k, before the heads split
    norm_eps: float = 1e-6
    # "gelu" (w_in, w_out) | "swiglu" (gated silu) | "moe" | "none": the
    # layer is its mixer alone, behind its one norm
    ffn: str = "gelu"
    n_experts: int = 0  # ffn == "moe": experts of width d_ff, gated silu
    top_k: int = 0  # experts a token; raw softmax probabilities gate them
    router_aux_coef: float = 0.0  # x load-balancing loss, added to the loss
    router_z_coef: float = 0.0  # x router z-loss
    tied_head: bool = True  # False: `lm_head` (V, D) of its own
    attn_core: str = "dense"  # or "flash": ops.flash_attention
    flash_blocks: Tuple[int, int] = (512, 512)
    flash_interpret: bool = False  # the tests' CPU mesh; never chosen by backend
    # a head size of its own, or fewer key/value heads than query heads
    # (query head h reads key/value head h // (n_heads // n_kv_heads)): either
    # gives the layer wq, wk, wv and wo of their own widths in wqkv's place
    head_size: int = 0  # 0: d_model // n_heads
    n_kv_heads: int = 0  # 0: n_heads
    window: int = 0  # w: query i sees key j iff 0 <= i - j < w; 0: every earlier key
    rotary_share: float = 1.0  # the leading share of each head that rope rotates
    # () or YaRN's (factor, original positions, beta_fast, beta_slow,
    # attention_factor): blended frequencies, cos and sin times the factor
    yarn: Tuple = ()
    head_gate: bool = False  # sigmoid(h @ w_head_gate), one a query head, on the core's output
    gates: str = "raw"  # or "renorm": the chosen experts' probabilities over their sum
    routed_scale: float = 1.0  # times the routed experts' gates
    # (first, count): the router sees n_experts, this chip holds `count` of
    # them from `first` and computes their part of the layer; (): all
    experts_held: Tuple = ()
    shared_ff: int = 0  # a gated-silu expert of this width that every token takes
    # the layer scan keeps a layer's input alone and runs the layer again in
    # the backward pass, where what the pieces keep of it would not fit
    layer_remat: bool = False
    # the layer's token mixer: softmax "attention" over the fields above, or
    # "gated_delta", the gated delta rule (`ops.gated_delta`) over
    # `delta_heads` = (key heads, value heads, head size) behind a causal
    # depthwise convolution of `conv_taps` taps; "mamba2", the Mamba-2
    # state-space mixer (`ops.ssm_scan`) over `ssm_dims` = (heads, head size,
    # state size, groups of heads that share B and C), its convolution with
    # a bias; "short_conv", LFM2's gated short convolution (`ops.short_conv`)
    # of `conv_taps` taps between two gates; or "none": the layer is its
    # feed-forward alone, behind its one norm
    mixer: str = "attention"
    delta_heads: Tuple = ()
    conv_taps: int = 4
    ssm_dims: Tuple = ()
    norm_offset: bool = False  # every RMSNorm's scale is 1 + w, w from 0
    # with wq, wk, wv of their own (`split_qkv`), `qk_norm` is an RMSNorm a
    # head over the head size, q's and k's scales (head size,) each.
    # q_gate: wq is twice as wide, a head's q and then its gate, and
    # sigmoid(gate), one a feature, is on the core's output
    q_gate: bool = False
    shared_gate: bool = False  # sigmoid(h @ w_shared_gate (D, 1)) on the shared expert
    # mixer "latent": (q latent rank, key/value latent rank, unrotated
    # features a q/k head, rotated features a q/k head, features a value
    # head); the rotated key is one for all heads, rotate-half at rope_theta
    latent_dims: Tuple = ()
    router_scores: str = "softmax"  # or "sigmoid": each expert's own score
    # a leaf `router_bias` (n_experts,) added to the scores for the choice
    # and not for the weight; the loss is constant in it
    router_bias: bool = False
    # the routed experts' function, and the shared expert's: "swiglu", three
    # matrices, w_down (silu(w_gate x) * w_up x), or "relu2", two, w_down
    # (relu(w_up x))^2
    expert_act: str = "swiglu"
    # multi-token prediction (DeepSeek-V3's, section 2.2): modules after the
    # stack (0 or 1), each one further block of the last layer's kind, and
    # the weight of their loss beside the main one
    mtp_depth: int = 0
    mtp_weight: float = 0.0
    # a looped model (Ouro's, arXiv:2510.25741): the stacks are run this many
    # times on the one set of weights, the final norm at the end of every
    # loop step; more than 1 brings an exit gate (`exit_gate_w`, `_b`) and
    # the expected loss over the loop steps' head passes, less
    # `exit_entropy_coef` times the entropy of the exit distribution
    loop_steps: int = 1
    exit_entropy_coef: float = 0.0
    # a second RMSNorm a branch, on the branch's output before the residual
    # takes it (`ln1_post_scale`, `ln2_post_scale`)
    post_norms: bool = False
    # Granite's four multipliers: the embedding's rows times the first, a
    # branch's output times `residual_multiplier` where the residual takes
    # it, the attention scores times `attention_multiplier` in the place of
    # 1 / sqrt(head size) (0: that), the logits over `logits_scaling`
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # packed documents: the id that is a document's last position. Each
    # position's document is numbered from the ids (`_segments`) and every
    # mixer keeps to it: no tap, state or key of another document. None: a
    # row is one document
    end_of_document: int | None = None
    # one tuple of (field, value) pairs a layer: what replaces the fields
    # above for that layer; (): every layer is the configuration's own
    layer_kinds: Tuple = ()
    # learned sparse attention (`ops.sparse_attention`): (indexer heads,
    # indexer head size, keys a query); every attention layer scores its
    # (query, key) pairs with a lightning indexer, attends over the so many
    # best-scored keys at or before each query, and adds the indexer's KL
    # loss times `indexer_loss_weight` to the model's. (): no indexer
    sparse_index: Tuple = ()
    indexer_loss_weight: float = 1.0

    def __post_init__(self):
        for field, value, known in (
                ("positions", self.positions, ("learned", "rope", "none")),
                ("ffn", self.ffn, ("gelu", "swiglu", "moe", "none")),
                ("expert_act", self.expert_act, ("swiglu", "relu2")),
                ("attn_core", self.attn_core, ("dense", "flash")),
                ("gates", self.gates, ("raw", "renorm")),
                ("mixer", self.mixer, ("attention", "gated_delta", "latent",
                                       "mamba2", "short_conv", "none")),
                ("router_scores", self.router_scores, ("softmax", "sigmoid"))):
            if value not in known:
                raise ValueError(f"{field} {value!r} is not one of {known}")
        if self.ffn == "moe" and not 1 <= self.top_k <= self.n_experts:
            raise ValueError(f"ffn 'moe' needs 1 <= top_k <= n_experts, got "
                             f"{self.top_k} of {self.n_experts}")
        if self.n_heads % self.kv_heads:
            raise ValueError(f"{self.n_heads} query heads are no multiple of "
                             f"{self.kv_heads} key/value heads")
        if self.q_gate and not self.split_qkv:
            raise ValueError("q_gate doubles wq, which a layer has with a head "
                             "size or key/value heads of its own (`split_qkv`)")
        if self.mixer == "gated_delta" and not (
                len(self.delta_heads) == 3 and self.delta_heads[0] >= 1
                and self.delta_heads[1] % self.delta_heads[0] == 0):
            raise ValueError("mixer 'gated_delta' needs delta_heads = (key "
                             "heads, value heads a multiple of them, head "
                             f"size), got {self.delta_heads}")
        if self.mixer == "none" and self.ffn == "none":
            raise ValueError("a layer is a mixer, a feed-forward or both: "
                             "mixer 'none' with ffn 'none' is no layer")
        if self.mixer == "mamba2" and not (
                len(self.ssm_dims) == 4 and min(self.ssm_dims) >= 1
                and self.ssm_dims[0] % self.ssm_dims[3] == 0):
            raise ValueError("mixer 'mamba2' needs ssm_dims = (heads, head "
                             "size, state size, groups that divide the "
                             f"heads), got {self.ssm_dims}")
        if self.mixer == "latent":
            if not (len(self.latent_dims) == 5 and min(self.latent_dims) >= 1
                    and self.latent_dims[3] % 2 == 0):
                raise ValueError("mixer 'latent' needs latent_dims = (q rank, "
                                 "key/value rank, unrotated, rotated (even), "
                                 f"value features a head), got {self.latent_dims}")
            if self.positions != "rope":
                raise ValueError("mixer 'latent' turns its rotated features "
                                 "by positions 'rope'")
            _, _, nope, rope, value = self.latent_dims
            if int((nope + rope) * (rope / (nope + rope))) != rope:
                raise ValueError(
                    f"{rope} rotated of {nope + rope} features is a share "
                    "that the rotary pass (`_rope`) rounds down")
            if self.attn_core == "flash" and nope + rope != value:
                raise ValueError(
                    f"the flash core has one head size: q/k heads of {nope} + "
                    f"{rope} and value heads of {value} need the dense core")
        if self.mixer == "short_conv":
            if self.conv_taps != 3:
                raise ValueError("mixer 'short_conv' convolves over LFM2's 3 "
                                 f"taps, got conv_taps {self.conv_taps}")
            if self.loop_steps > 1 or self.mtp_depth:
                raise ValueError("mixer 'short_conv' is built for the plain "
                                 "stack: not under a loop (loop_steps > 1) nor "
                                 "in a model with a multi-token-prediction "
                                 "module, which no test holds it to")
        if self.mtp_depth not in (0, 1):
            raise ValueError(f"mtp_depth {self.mtp_depth}: one multi-token-"
                             "prediction module or none")
        if self.shared_gate and not self.shared_ff:
            raise ValueError("shared_gate gates the shared expert (shared_ff)")
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps {self.loop_steps}: the stacks are "
                             "run once or more")
        if self.exit_entropy_coef and self.loop_steps == 1:
            raise ValueError("exit_entropy_coef weighs the exit distribution "
                             "of a loop (loop_steps > 1)")
        if self.loop_steps > 1 and (self.mtp_depth or (
                self.ffn == "moe" and not self.layer_kinds)):
            raise ValueError("a loop (loop_steps > 1) has no place for an "
                             "expert layer's losses and counters a loop step, "
                             "nor for a multi-token-prediction module")
        if self.sparse_index:
            self._check_sparse_index()
        if (self.window or self.kv_heads != self.n_heads
                or self.attention_multiplier) and self.attn_core != "flash" \
                and not self.sparse_index:
            raise ValueError("a window, grouped heads and a scale of the "
                             "scores' own are the flash core's (attn_core "
                             "'flash'); the dense core has none of them")
        if self.end_of_document is not None and (
                self.mtp_depth or self.mixer in ("gated_delta", "latent")
                or (self.mixer == "attention" and self.attn_core != "flash")):
            raise ValueError(
                "packed documents (end_of_document) are kept apart by the "
                "Mamba-2 mixer, the short convolution and the flash core of "
                "softmax attention: "
                f"not by mixer {self.mixer!r} on the {self.attn_core} core, "
                "nor by a multi-token-prediction module")
        if self.layer_kinds and len(self.layer_kinds) != self.n_layers:
            raise ValueError(f"{len(self.layer_kinds)} layer kinds for "
                             f"{self.n_layers} layers")
        for kind in self.layer_kinds:
            dataclasses.replace(self, layer_kinds=(), n_layers=1, **dict(kind))

    def _check_sparse_index(self):
        """What a learned sparse index stands with, each refusal a sentence."""
        if not (len(self.sparse_index) == 3 and min(self.sparse_index) >= 1
                and self.sparse_index[1] % 2 == 0):
            raise ValueError("sparse_index is (indexer heads, an even indexer "
                             "head size, keys a query), got "
                             f"{self.sparse_index}")
        if self.mixer != "attention" or not self.split_qkv or (
                self.positions != "rope"):
            raise ValueError("sparse_index chooses the keys of softmax "
                             "attention with projections of its own (a head "
                             "size or key/value heads) and rotary positions, "
                             f"not of mixer {self.mixer!r} with positions "
                             f"{self.positions!r}")
        for field, what, unset in (
                ("window", "a window beside the choice", 0),
                ("end_of_document", "packed documents under the choice", None),
                ("mtp_depth", "a multi-token-prediction module", 0),
                ("layer_kinds", "layers that differ in kind", ()),
                ("head_gate", "a gate a head", False),
                ("q_gate", "a gate a feature", False),
                ("attention_multiplier", "a scale of the scores' own", 0.0),
                ("yarn", "YaRN's frequencies", ())):
            if getattr(self, field) != unset:
                raise ValueError(
                    f"sparse_index is not built with {what} ({field}): the "
                    "choice is made under the causal bound alone, in a stack "
                    "of one kind of layer, and no test holds it to more")
        if self.loop_steps > 1 or self.rotary_share != 1.0:
            raise ValueError("sparse_index is not built under a loop "
                             "(loop_steps > 1), which has no place for the "
                             "indexer's loss a loop step, nor with a rotary "
                             "share of the head (rotary_share)")

    @property
    def head_dim(self) -> int:
        if self.head_size:
            return self.head_size
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def split_qkv(self) -> bool:
        """wq, wk, wv, wo of their own widths in the place of wqkv."""
        return bool(self.head_size or self.n_kv_heads)

    @property
    def stacks(self) -> Tuple:
        """((configuration of one layer kind, its successive layers), ...)
        in the model's layer order; one stack of all layers where the
        layers do not differ."""
        if not self.layer_kinds:
            return ((self, self.n_layers),)
        runs = []
        for kind in self.layer_kinds:
            if runs and runs[-1][0] == kind:
                runs[-1][1] += 1
            else:
                runs.append([kind, 1])
        return tuple((dataclasses.replace(self, layer_kinds=(), n_layers=n,
                                          **dict(kind)), n)
                     for kind, n in runs)

    @property
    def mtp_kind(self) -> "TransformerConfig":
        """The configuration of the multi-token-prediction module's block:
        the last layer's kind, one layer of it."""
        return dataclasses.replace(self.stacks[-1][0], n_layers=1)

    @classmethod
    def bert_base(cls) -> "TransformerConfig":
        return cls(vocab_size=30522, d_model=768, n_heads=12, n_layers=12,
                   d_ff=3072, max_seq=512)

    @classmethod
    def tiny(cls) -> "TransformerConfig":
        return cls(vocab_size=256, d_model=64, n_heads=4, n_layers=2,
                   d_ff=128, max_seq=64)

    @classmethod
    def olmoe_1b_7b(cls, n_layers: int = 16, **changes) -> "TransformerConfig":
        """allenai/OLMoE-1B-7B-0125-Instruct's config.json: rotary, q/k
        norm, 64 gated-silu experts of width 1024, 8 a token, untied head.
        The auxiliary losses' coefficients are the OLMoE paper's
        (arXiv:2409.02060)."""
        return dataclasses.replace(cls(
            vocab_size=50304, d_model=2048, n_heads=16, n_layers=n_layers,
            d_ff=1024, max_seq=4096, positions="rope", rope_theta=10000.0,
            qk_norm=True, norm_eps=1e-5, ffn="moe", n_experts=64, top_k=8,
            router_aux_coef=0.01, router_z_coef=0.001, tied_head=False,
            attn_core="flash"), **changes)

    @classmethod
    def lfm2_24b_a2b(cls, n_layers: int = 40, **changes) -> "TransformerConfig":
        """LiquidAI/LFM2-24B-A2B's config.json (`model_type` lfm2_moe): a
        gated short convolution of 3 taps as the mixer of three layers in four
        (`conv, conv, full_attention, conv` ten times), attention of 32 query
        heads on 8 key/value heads of 64 with a q/k norm a head and rotary
        positions at 1e6 in the fourth, two leading dense layers of width
        11,776 and then 64 sigmoid-scored experts of width 1,536, 4 a token
        under a selection bias, the chosen scores renormalised; a tied head
        (the family's convention). `n_layers`: the model's first so many."""
        mixers = [(("mixer", "short_conv"),), (("mixer", "short_conv"),),
                  (("mixer", "attention"),), (("mixer", "short_conv"),)] * 10
        kinds = tuple(mixer + ((("ffn", "swiglu"), ("d_ff", 11776)) if l < 2
                               else (("ffn", "moe"), ("d_ff", 1536)))
                      for l, mixer in enumerate(mixers[:n_layers]))
        return dataclasses.replace(cls(
            vocab_size=65536, d_model=2048, n_heads=32, n_layers=n_layers,
            d_ff=1536, max_seq=128000, positions="rope", rope_theta=1e6,
            qk_norm=True, norm_eps=1e-5, ffn="moe", n_experts=64, top_k=4,
            tied_head=True, attn_core="flash", head_size=64, n_kv_heads=8,
            conv_taps=3, router_scores="sigmoid", router_bias=True,
            gates="renorm", routed_scale=1.0, layer_kinds=kinds), **changes)

    @classmethod
    def tiny_moe(cls, **changes) -> "TransformerConfig":
        """Every mechanism of `olmoe_1b_7b` on, at the tests' size; the
        flash core in interpret mode."""
        return dataclasses.replace(cls.olmoe_1b_7b(
            n_layers=2, vocab_size=256, d_model=64, n_heads=4, d_ff=32,
            max_seq=64, n_experts=8, top_k=3, flash_blocks=(32, 32),
            flash_interpret=True), **changes)


def init_transformer(key, cfg: TransformerConfig) -> Dict:
    """Params in f32; cast to cfg.dtype at apply time."""
    keys = jax.random.split(key, 2 + cfg.n_layers)
    scale = 0.02

    def dense(k, shape):
        return jax.random.normal(k, shape, jnp.float32) * scale

    D = cfg.d_model

    def unit(cfg, shape):
        """A norm's weight at the start: its scale is the weight, from 1, or
        1 + the weight, from 0."""
        return (jnp.zeros if cfg.norm_offset else jnp.ones)(shape, jnp.float32)

    def init_layer(key, cfg):
        # the gelu block draws what it always drew from four keys; the
        # other feed-forwards take further keys of a split of their own,
        # what PR 33 brought those of a second split, and the gated delta
        # mixer and the shared expert's gate (PR 36) those of a third
        F, E = cfg.d_ff, cfg.n_experts
        lk = jax.random.split(key, 4 if cfg.ffn == "gelu" else 6)
        if cfg.split_qkv or cfg.head_gate or cfg.shared_ff:
            xk = jax.random.split(jax.random.fold_in(key, 1), 6)
        if cfg.mixer == "gated_delta" or cfg.shared_gate:
            gk = jax.random.split(jax.random.fold_in(key, 2), 6)
        if cfg.mixer == "latent" or cfg.router_bias:  # PR 41's, a fourth
            mk = jax.random.split(jax.random.fold_in(key, 3), 5)
        # a layer of one branch has that branch's norm alone (PR 43)
        layer = {}
        if cfg.mixer != "none":
            layer["ln1_scale"] = unit(cfg, (D,))
        if cfg.ffn != "none":
            layer["ln2_scale"] = unit(cfg, (D,))
        if cfg.post_norms:  # one behind each branch the layer has
            for pre in list(layer):
                layer[pre.replace("_scale", "_post_scale")] = unit(cfg, (D,))
        if cfg.mixer == "none":
            pass
        elif cfg.mixer == "mamba2":
            H, hp, N, G = cfg.ssm_dims
            K, conv = cfg.conv_taps, H * hp + 2 * G * N
            sk = jax.random.split(jax.random.fold_in(key, 4), 5)  # a fifth
            # Mamba-2's own start (its `time_step_min`, `_max`, `_floor` and
            # `A_init_range`): A uniform on [1, 16], the step log-uniform on
            # [0.001, 0.1] and at least 1e-4, dt_bias its inverse softplus, D
            # 1; taps and bias as a depthwise Conv1d's default, uniform within
            # 1 / sqrt(K)
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                sk[3], (H,), jnp.float32, math.log(0.001), math.log(0.1))), 1e-4)
            layer.update(
                w_ssm_in=dense(sk[0], (D, H * hp + conv + H)),
                conv_w=jax.random.uniform(sk[1], (K, conv), jnp.float32,
                                          -K ** -0.5, K ** -0.5),
                conv_b=jax.random.uniform(sk[2], (conv,), jnp.float32,
                                          -K ** -0.5, K ** -0.5),
                A_log=jnp.log(jax.random.uniform(sk[4], (H,), jnp.float32,
                                                 1.0, 16.0)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                D_skip=jnp.ones((H,), jnp.float32),
                ssm_norm_scale=jnp.ones((H * hp,), jnp.float32),
                wo=dense(lk[1], (H * hp, D)))
        elif cfg.mixer == "short_conv":
            ck = jax.random.split(jax.random.fold_in(key, 5), 3)  # a sixth
            layer.update(conv_in=dense(ck[0], (D, 3 * D)),
                         conv_w=dense(ck[1], (cfg.conv_taps, D)),
                         conv_out=dense(ck[2], (D, D)))
        elif cfg.mixer == "gated_delta":
            Hk, Hv, d = cfg.delta_heads
            K = cfg.conv_taps
            # the decay's parameters as the Gated DeltaNet reference
            # implementation draws them (Mamba2's): A uniform in (0, 16),
            # dt log-uniform in (0.001, 0.1) and dt_bias its inverse
            # softplus, so g = -A softplus(a + dt_bias) is about -A dt at
            # the start, from a memory of a thousand positions to one of
            # less than one, head by head; the taps as a depthwise Conv1d's
            # default, uniform within 1 / sqrt(K)
            dt = jnp.exp(jax.random.uniform(gk[4], (Hv,), jnp.float32,
                                            math.log(0.001), math.log(0.1)))
            layer.update(
                w_qkvz=dense(gk[0], (D, 2 * (Hk + Hv) * d)),
                w_ba=dense(gk[1], (D, 2 * Hv)),
                conv_w=jax.random.uniform(gk[2], (K, (2 * Hk + Hv) * d),
                                          jnp.float32, -K ** -0.5, K ** -0.5),
                A_log=jnp.log(jax.random.uniform(gk[3], (Hv,), jnp.float32,
                                                 1e-3, 16.0)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                gdn_norm_scale=jnp.ones((d,), jnp.float32),
                wo=dense(lk[1], (Hv * d, D)))
        elif cfg.mixer == "latent":
            # the published layout: W_q_up's columns a head at a time, its
            # unrotated features and then its rotated; W_kv_down's the
            # latent and then the one rotated key; W_kv_up's a head at a
            # time, its unrotated key features and then its value
            rq, rkv, nope, rope, value = cfg.latent_dims
            H = cfg.n_heads
            layer.update(
                w_q_down=dense(mk[0], (D, rq)),
                q_latent_norm=unit(cfg, (rq,)),
                w_q_up=dense(mk[1], (rq, H * (nope + rope))),
                w_kv_down=dense(mk[2], (D, rkv + rope)),
                kv_latent_norm=unit(cfg, (rkv,)),
                w_kv_up=dense(mk[3], (rkv, H * (nope + value))),
                wo=dense(lk[1], (H * value, D)))
        elif cfg.split_qkv:
            q_width, kv_width = (h * cfg.head_dim
                                 for h in (cfg.n_heads, cfg.kv_heads))
            layer["wq"] = dense(lk[0], (D, q_width * (2 if cfg.q_gate else 1)))
            layer["wk"] = dense(xk[0], (D, kv_width))
            layer["wv"] = dense(xk[1], (D, kv_width))
            layer["wo"] = dense(lk[1], (q_width, D))
        else:
            layer["wqkv"] = dense(lk[0], (D, 3 * D))
            layer["wo"] = dense(lk[1], (D, D))
        if cfg.head_gate and cfg.mixer == "attention":
            layer["w_head_gate"] = dense(xk[2], (D, cfg.n_heads))
        if cfg.sparse_index:  # the lightning indexer's five, from a seventh
            Hi, di, _ = cfg.sparse_index
            ik = jax.random.split(jax.random.fold_in(key, 6), 3)
            layer.update(index_wq=dense(ik[0], (D, Hi * di)),
                         index_wk=dense(ik[1], (D, di)),
                         index_w=dense(ik[2], (D, Hi)),
                         index_ln_scale=jnp.ones((di,), jnp.float32),
                         index_ln_bias=jnp.zeros((di,), jnp.float32))
        gated = cfg.ffn == "swiglu" or cfg.expert_act == "swiglu"
        if cfg.ffn == "gelu":
            layer["w_in"] = dense(lk[2], (D, F))
            layer["w_out"] = dense(lk[3], (F, D))
        elif cfg.ffn != "none":
            held = cfg.experts_held[1] if cfg.experts_held else E
            stack = (held,) if cfg.ffn == "moe" else ()
            if gated:
                layer["w_gate"] = dense(lk[2], stack + (D, F))
            layer["w_up"] = dense(lk[3], stack + (D, F))
            layer["w_down"] = dense(lk[4], stack + (F, D))
        if cfg.ffn == "moe":
            layer["router"] = dense(lk[5], (D, E))
            if cfg.router_bias:
                # no balancing step moves it here (`make_train_step` updates
                # what gradients update): drawn small against the spread of
                # the scores, so that choice and weight differ
                layer["router_bias"] = 0.01 * jax.random.normal(
                    mk[4], (E,), jnp.float32)
            if cfg.shared_ff:
                if gated:
                    layer["shared_gate"] = dense(xk[3], (D, cfg.shared_ff))
                layer["shared_up"] = dense(xk[4], (D, cfg.shared_ff))
                layer["shared_down"] = dense(xk[5], (cfg.shared_ff, D))
            if cfg.shared_gate:
                layer["w_shared_gate"] = dense(gk[5], (D, 1))
        if cfg.qk_norm and cfg.mixer == "attention":
            width = cfg.head_dim if cfg.split_qkv else D
            layer["q_norm_scale"] = unit(cfg, (width,))
            layer["k_norm_scale"] = unit(cfg, (width,))
        return layer

    # stack layers: leading axis = layer, enables lax.scan over layers; a
    # stack for each run of layers of one kind
    stacks, at = [], 2
    for kind, n in cfg.stacks:
        layers = [init_layer(keys[at + i], kind) for i in range(n)]
        stacks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *layers))
        at += n
    params = {
        "embed": dense(keys[0], (cfg.vocab_size, D)),
        "ln_f_scale": unit(cfg, (D,)),
        "layers": tuple(stacks) if cfg.layer_kinds else stacks[0],
    }
    if cfg.positions == "learned":
        params["pos_embed"] = dense(keys[1], (cfg.max_seq, D))
    if not cfg.tied_head:
        params["lm_head"] = dense(jax.random.fold_in(keys[1], 1),
                                  (cfg.vocab_size, D))
    if cfg.loop_steps > 1:
        params["exit_gate_w"] = dense(jax.random.fold_in(keys[1], 3), (D, 1))
        params["exit_gate_b"] = jnp.zeros((), jnp.float32)
    if cfg.mtp_depth:
        tk = jax.random.split(jax.random.fold_in(keys[1], 2), 2)
        params["mtp"] = {
            "enorm_scale": unit(cfg, (D,)),
            "hnorm_scale": unit(cfg, (D,)),
            "eh_proj": dense(tk[0], (2 * D, D)),
            "layer": init_layer(tk[1], cfg.mtp_kind),
            "ln_f_scale": unit(cfg, (D,)),
        }
    return params


def param_pspecs(cfg: TransformerConfig, tp_axis: str = "tp",
                 ep_axis: str = "ep") -> Dict:
    """PartitionSpec tree matching init_transformer's param tree, whatever
    the layer is.

    Column-parallel wqkv (or wq, wk, wv and the head gate)/w_in/w_gate/w_up
    (shard output features over tp), row-parallel wo/w_out/w_down (shard
    input features over tp), a shared expert like a gated-silu
    feed-forward (two matrices each under `expert_act` "relu2"); a layer of
    one branch has that branch's leaves alone; a Mamba-2 mixer's fused
    projection, taps, bias and gated norm are column-parallel, its numbers a
    head whole; a short-convolution mixer's `conv_in` and taps column-parallel
    and its `conv_out` row-parallel; embedding and an untied head sharded over vocab; an
    expert stack over `ep_axis` on its expert dimension, the router whole.
    Layer-stacked leaves have a leading layer axis (unsharded); a
    configuration with `layer_kinds` has a tuple of such stacks. The q/k
    norms' scales span all of q's features, which tp splits: sharded like
    them; a head's own (split projections) are whole. A gated delta mixer's
    fused projection and taps are column-parallel. A latent mixer's
    up-projections are a head at a time and column-parallel, its
    down-projections (the one rotary key's columns among them) and the
    latents' norms whole. A multi-token-prediction module's block is
    sharded like a layer of its kind, its norms and projection whole. A
    lightning indexer's five leaves (`sparse_index`) are whole: every shard of
    the heads attends under the one choice.
    """
    t, e = tp_axis, ep_axis

    def stack_specs(cfg):
        layers = {}
        if cfg.mixer != "none":
            layers.update(ln1_scale=P(None))
            if cfg.mixer != "short_conv":
                layers.update(wo=P(None, t, None))
        if cfg.ffn != "none":
            layers.update(ln2_scale=P(None))
        if cfg.post_norms:
            layers.update({name.replace("_scale", "_post_scale"): P(None)
                           for name in list(layers) if name.startswith("ln")})
        if cfg.mixer == "none":
            pass
        elif cfg.mixer == "mamba2":
            # the fused projection's, the convolution's and the gated norm's
            # channels over tp like any column-parallel matrix's; a number a
            # head whole
            layers.update(w_ssm_in=P(None, None, t), conv_w=P(None, None, t),
                          conv_b=P(None, t), A_log=P(None, None),
                          dt_bias=P(None, None), D_skip=P(None, None),
                          ssm_norm_scale=P(None, t))
        elif cfg.mixer == "short_conv":
            # the projection's columns over tp like any column-parallel
            # matrix's (a shard holds a slice of B, C and x each, the
            # partitioner's affair), the taps with the channels, W_out's rows
            layers.update(conv_in=P(None, None, t), conv_w=P(None, None, t),
                          conv_out=P(None, t, None))
        elif cfg.mixer == "gated_delta":
            # the fused projection's and the convolution's channels over tp
            # like any column-parallel matrix; a number a head and the
            # norm's scale whole
            layers.update(w_qkvz=P(None, None, t), w_ba=P(None, None, None),
                          conv_w=P(None, None, t), A_log=P(None, None),
                          dt_bias=P(None, None), gdn_norm_scale=P(None, None))
        elif cfg.mixer == "latent":
            layers.update(w_q_down=P(None, None, None), q_latent_norm=P(None, None),
                          w_q_up=P(None, None, t),
                          w_kv_down=P(None, None, None),
                          kv_latent_norm=P(None, None),
                          w_kv_up=P(None, None, t))
        elif cfg.split_qkv:  # heads over tp, as wqkv's columns are
            layers.update(wq=P(None, None, t), wk=P(None, None, t),
                          wv=P(None, None, t))
        else:
            layers.update(wqkv=P(None, None, t))
        if cfg.head_gate and cfg.mixer == "attention":
            layers.update(w_head_gate=P(None, None, t))
        if cfg.sparse_index:  # the indexer whole on every chip: one choice
            layers.update(index_wq=P(None, None, None), index_wk=P(None, None, None),
                          index_w=P(None, None, None), index_ln_scale=P(None, None),
                          index_ln_bias=P(None, None))
        if cfg.ffn == "gelu":
            layers.update(w_in=P(None, None, t), w_out=P(None, t, None))
        elif cfg.ffn == "swiglu":
            layers.update(w_gate=P(None, None, t), w_up=P(None, None, t),
                          w_down=P(None, t, None))
        elif cfg.ffn == "moe":
            gated = cfg.expert_act == "swiglu"
            layers.update(w_up=P(None, e, None, t), w_down=P(None, e, t, None),
                          router=P(None, None, None))
            if gated:
                layers.update(w_gate=P(None, e, None, t))
            if cfg.router_bias:
                layers.update(router_bias=P(None, None))
            if cfg.shared_ff:
                layers.update(shared_up=P(None, None, t),
                              shared_down=P(None, t, None))
                if gated:
                    layers.update(shared_gate=P(None, None, t))
            if cfg.shared_gate:
                layers.update(w_shared_gate=P(None, None, None))
        if cfg.qk_norm and cfg.mixer == "attention":
            # over all of q's features, which tp splits, or over one head's
            spec = P(None, None) if cfg.split_qkv else P(None, t)
            layers.update(q_norm_scale=spec, k_norm_scale=spec)
        return layers

    stacks = tuple(stack_specs(kind) for kind, _ in cfg.stacks)
    specs = {"embed": P(t, None), "ln_f_scale": P(),
             "layers": stacks if cfg.layer_kinds else stacks[0]}
    if cfg.positions == "learned":
        specs["pos_embed"] = P()
    if not cfg.tied_head:
        specs["lm_head"] = P(t, None)
    if cfg.loop_steps > 1:  # the exit gate whole on every chip
        specs.update(exit_gate_w=P(None, None), exit_gate_b=P())
    if cfg.mtp_depth:
        specs["mtp"] = {
            "enorm_scale": P(), "hnorm_scale": P(), "eh_proj": P(None, None),
            "ln_f_scale": P(),
            # one layer, with no layer axis in front
            "layer": {name: P(*spec[1:])
                      for name, spec in stack_specs(cfg.mtp_kind).items()}}
    return specs


# What the backward pass keeps (PERF.md, PR 25). The layer scan stacks every
# residual of its body once a layer, so each piece below whose residuals are
# cheap functions of something smaller that is saved anyway says so itself
# with `jax.checkpoint`: it keeps its inputs and recomputes the rest where
# the backward pass wants it. One HBM byte costs the v5e 240 operations, so
# an S x S probability array (12 bytes an element, written and read) is
# worth 2,900 operations against the 128 of a second QK^T, at every length.
# `prevent_cse=False`: inside a scan body the barrier is unnecessary and
# costs fusions.
_recompute = functools.partial(jax.checkpoint, prevent_cse=False)


def _rmsnorm(x, scale, eps=1e-6):
    """Keeps x and scale; the f32 upcast, the variance and the normalised
    output are recomputed. `eps` is data of the configuration, not of the
    program: a Python number."""
    return _rmsnorm_at(x, scale, eps)


@functools.partial(_recompute, static_argnums=(2,))
def _rmsnorm_at(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


@_recompute
def _full_attention_core(q, k, v):
    """(B, H, S, hd) q/k/v -> causal attention context, same shape.

    Keeps q, k, v; scores, mask, the f32 softmax and its cast are
    recomputed. The checkpoint is this core's own, not `_attention`'s or
    `_block`'s: a core plugged from outside (the ring, flash attention's
    `custom_vjp`) keeps its own residuals and is never run twice."""
    hd = q.shape[-1]
    S = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(hd).astype(q.dtype)
    mask = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@_recompute
def _gelu_out(pre, w_out):
    """gelu(pre) @ w_out. Keeps the pre-activation and w_out; the
    tanh-gelu, its four temporaries and with them the matmul's operand are
    recomputed. Saving the gelu's output for that matmul instead was 0.15
    ms a step slower at bert_base's size and 0.6 GB larger (PERF.md, PR 25)."""
    return jax.nn.gelu(pre) @ w_out


def _yarn_ramp(rd: int, theta: float, yarn: Tuple):
    """YaRN's blend (arXiv:2309.00071, as the transformers library's
    `_compute_yarn_parameters` computes it): 0 for the rd // 2 frequencies
    that turn more than beta_fast times over the original positions and
    keep their own frequency, 1 for those that turn fewer than beta_slow
    times and take theirs over `factor`, linear between."""
    _, original, beta_fast, beta_slow, _ = yarn

    def dim_of(turns):
        return (rd * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), rd - 1)
    span = (high - low) or 0.001
    return jnp.clip((jnp.arange(rd // 2, dtype=jnp.float32) - low) / span, 0, 1)


def _rotary_tables(S: int, hd: int, theta: float, share: float, yarn: Tuple):
    """(S, hd) float32 cos and sin of positions 0..S-1 for the rotate-half
    form over the leading `share` of the head: the rd // 2 frequencies on
    both halves of the rotated features, under `yarn` its blended
    frequencies and its attention factor on both tables, and cos 1, sin 0
    on the features that pass through. Traced `jnp` of static shapes: made
    again inside the program wherever a pass wants them."""
    rd = int(hd * share)  # the rotated features
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    if yarn:
        ramp = _yarn_ramp(rd, theta, yarn)
        inv_freq = inv_freq / yarn[0] * ramp + inv_freq * (1 - ramp)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)  # (S, rd // 2)
    if yarn:
        cos, sin = cos * yarn[4], sin * yarn[4]
    through = jnp.ones((S, hd - rd), jnp.float32)
    return (jnp.concatenate([cos, cos, through], axis=-1),
            jnp.concatenate([sin, sin, jnp.zeros_like(through)], axis=-1))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _turned(t, rule: Tuple, back: bool):
    """One pass over (B, H, S, hd) t (`ops.rotary.rotate`): cos * t + sin *
    P t, P the signed swap of the two halves of the rotated features, or,
    `back`, its transpose cos * t - sin * P t on a cotangent; the sign of P
    rides in the sine table. The kernel reads t as (B, S, H * hd) and writes
    (B, H, S, hd), and the other way about on the way back: the transposition
    below undoes the caller's own, so a projection's output goes to the
    attention core through this one pass. Mosaic where the program is
    lowered for the TPU, the same kernel interpreted anywhere else. Under a
    `jax.jit` of its own, so that a step's calls of one shape trace and
    lower one body: the Laguna cell's first step is 2 s shorter warm and 9 s
    cold for it on the chip's host (PERF.md, PR 35)."""
    from kungfu_tpu.ops.rotary import rotate

    theta, share, yarn = rule
    B, H, S, hd = t.shape
    half = int(hd * share) // 2
    cos, sin = _rotary_tables(S, hd, theta, share, yarn)
    sin = jnp.where((jnp.arange(hd) < half) != back, -sin, sin)
    if not back:
        t = t.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    kernel = functools.partial(rotate, half=half, into_heads=not back)
    out = jax.lax.platform_dependent(
        t, cos, sin, tpu=kernel,
        default=functools.partial(kernel, interpret=True))
    return out.reshape(B, S, H, hd).transpose(0, 2, 1, 3) if back else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rotated(t, rule: Tuple):
    """The rotation is linear in t and its transpose is the same pass with
    the sine's sign turned, so the backward pass needs nothing of t: no
    residual, and no transposed slices (pads) and concatenations (slices
    and adds) of q's size in float32, which is what autodiff writes for the
    rotate-half form (33.7 ms of the Laguna cell's step, PERF.md, PR 35)."""
    return _turned(t, rule, False)


_rotated.defvjp(lambda t, rule: (_turned(t, rule, False), None),
                lambda rule, _, dy: (_turned(dy, rule, True),))


def _rope(q, k, theta: float, share: float, yarn: Tuple):
    """Rotary positions on (B, H, S, hd) q and k (each its own H),
    positions 0..S-1: the rotate-half form over the leading `share` of the
    head dimension (the rest passes through), angles and the rotation in
    float32, under `yarn` its frequencies and attention factor. Keeps
    nothing for the backward pass."""
    rule = (theta, share, yarn)
    return _rotated(q, rule), _rotated(k, rule)


@_recompute
def _silu_gate_out(gate, up, w_down):
    """(silu(gate) * up) @ w_down. Keeps gate, up and w_down; the silu and
    the product are recomputed, as `_gelu_out` recomputes its gelu."""
    return (jax.nn.silu(gate) * up) @ w_down


@_recompute
def _relu2_out(up, w_down):
    """relu(up)^2 @ w_down. Keeps up and w_down; the square, the matmul's
    operand, is recomputed, as `_gelu_out` recomputes its gelu."""
    return jnp.square(jax.nn.relu(up)) @ w_down


def attention_core_of(cfg: TransformerConfig):
    """The (q, k, v) -> ctx core the configuration names."""
    if cfg.attn_core == "dense":
        return _full_attention_core
    from kungfu_tpu.ops.flash_attention import flash_attention

    blk_q, blk_k = cfg.flash_blocks
    return lambda q, k, v, *segments: flash_attention(
        q, k, v, True, cfg.attention_multiplier or None, blk_q, blk_k,
        cfg.flash_interpret, cfg.window or None, *segments)


def _gated_out(ctx, pre, wo):
    """(ctx (B, H, S, hd) times sigmoid(pre (B, S, H)), a scalar a head and
    position, the sigmoid in float32) as (B, S, H * hd) @ wo. Under its
    checkpoint (`_gated_out_kept`) it keeps ctx, which the core keeps
    anyway, pre and wo; the gated copy of ctx, the matmul's operand, is made
    again, as `_gelu_out` makes its gelu again."""
    B, H, S, hd = ctx.shape
    gate = jax.nn.sigmoid(pre.astype(jnp.float32)).astype(ctx.dtype)
    ctx = ctx * gate.transpose(0, 2, 1)[..., None]
    return ctx.transpose(0, 2, 1, 3).reshape(B, S, H * hd) @ wo


def _split_heads(x, wqkv, cfg, qk_scales=None):
    """x @ (wq, wk, wv) as (B, heads, S, hd) q, k, v with rotary positions,
    and the (B, S, H, hd) gate that a doubled wq carries behind each head's
    q (`q_gate`; None without). q and k are normed a head where the
    configuration says so (`qk_scales`). Without a norm the backward pass
    wants x and the matrices and nothing else: the rotation keeps nothing,
    so there is no checkpoint to say so."""
    B, S, _ = x.shape
    hd = cfg.head_dim

    def heads(t):
        return t.transpose(0, 2, 1, 3)

    gate = None
    if cfg.q_gate:
        q = (x @ wqkv[0]).reshape(B, S, -1, 2 * hd)
        q, gate = q[..., :hd], q[..., hd:]
    else:
        q = (x @ wqkv[0]).reshape(B, S, -1, hd)
    if not cfg.qk_norm:
        q = heads(q)
    k = (x @ wqkv[1]).reshape(B, S, -1, hd)
    if cfg.qk_norm:
        with jax.named_scope("qk_norm"):
            q = heads(_rmsnorm(q, qk_scales[0], cfg.norm_eps))
            k = _rmsnorm(k, qk_scales[1], cfg.norm_eps)
    k = heads(k)
    v = heads((x @ wqkv[2]).reshape(B, S, -1, hd))
    if cfg.positions == "rope":
        with jax.named_scope("rope"):
            q, k = _rope(q, k, cfg.rope_theta, cfg.rotary_share, cfg.yarn)
    return q, k, v, gate


def _feature_gated_out(ctx, gate, wo):
    """(ctx (B, H, S, hd) times sigmoid(gate (B, S, H, hd)), one a feature,
    the sigmoid in float32) as (B, S, H * hd) @ wo. Under its checkpoint
    (`_feature_gated_out_kept`) it keeps ctx, gate and wo and makes the
    gated copy again, as `_gated_out` does."""
    B, H, S, hd = ctx.shape
    ctx = ctx.transpose(0, 2, 1, 3) * jax.nn.sigmoid(
        gate.astype(jnp.float32)).astype(ctx.dtype)
    return ctx.reshape(B, S, H * hd) @ wo


# in a layer that is run again whole (`layer_remat`) the piece as it is: a
# checkpoint inside would run it a third time
_gated_out_kept = _recompute(_gated_out)
_feature_gated_out_kept = _recompute(_feature_gated_out)


def _attention(x, wqkv, wo, cfg: TransformerConfig, core=None, qk_scales=None,
               w_head_gate=None, segments=()):
    """QKV projection + head reshape around a pluggable (q,k,v)->ctx core
    (the configuration's by default, the ring core for sequence parallelism
    — ONE copy of the projection plumbing for every path). `wqkv` is the
    fused (D, 3D) matrix, or (wq, wk, wv) where q's width and k's, v's are
    the configuration's own (`split_qkv`), wq twice as wide where it carries
    a gate a feature (`q_gate`). `qk_scales` = (q_norm_scale, k_norm_scale)
    where the configuration norms q and k, over all of their features or,
    with split projections, a head; `w_head_gate` (D, H) where it gates each
    head's output; `segments`, (the documents' numbers,) of packed rows, go
    to the core."""
    B, S, _ = x.shape
    H, hd = cfg.n_heads, cfg.head_dim
    gate = None
    if cfg.split_qkv:
        q, k, v, gate = _split_heads(x, wqkv, cfg, qk_scales)
    else:
        qkv = x @ wqkv  # (B, S, 3D)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        if cfg.qk_norm:
            with jax.named_scope("qk_norm"):
                q = _rmsnorm(q, qk_scales[0], cfg.norm_eps)
                k = _rmsnorm(k, qk_scales[1], cfg.norm_eps)
        q = q.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
        k = k.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
        v = v.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
        if cfg.positions == "rope":
            with jax.named_scope("rope"):
                q, k = _rope(q, k, cfg.rope_theta, cfg.rotary_share, cfg.yarn)
    with _core_kind_scope(cfg), jax.named_scope("attn_core"):
        ctx = (core or attention_core_of(cfg))(q, k, v, *segments)
    if cfg.head_gate:
        with jax.named_scope("attn_gate"):
            gated_out = _gated_out if cfg.layer_remat else _gated_out_kept
            return gated_out(ctx, x @ w_head_gate, wo)
    if gate is not None:
        with jax.named_scope("attn_gate"):
            gated_out = (_feature_gated_out if cfg.layer_remat
                         else _feature_gated_out_kept)
            return gated_out(ctx, gate, wo)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    return ctx @ wo


class LayerAux(NamedTuple):
    """What a layer with a learned sparse index (`sparse_index`) hands the
    loss beside its hidden states: `moe`, the expert layer's `ops.moe.MoeAux`
    (None of any other feed-forward), and `index_kl`, the indexer's KL loss
    of the layer, a float32 scalar. A layer without an indexer hands its
    `MoeAux` or None as it is."""
    moe: Any
    index_kl: Any


def _aux_parts(aux):
    """-> (the expert layers' aux or None, the indexers' KL losses a layer or
    None) of what a layer scan stacked."""
    return tuple(aux) if isinstance(aux, LayerAux) else (aux, None)


def _layer_norm(x, scale, bias, eps):
    """LayerNorm over the last axis, float32: (x - mean) / sqrt(var + eps) *
    scale + bias."""
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                              + eps) * scale + bias)


def _rotate_half(t, cos, sin):
    """t cos + rotate_half(t) sin over the last axis, cos and sin of its
    width: the plain form, for the indexer's small float32 arrays."""
    half = t.shape[-1] // 2
    return t * cos + jnp.concatenate([-t[..., half:], t[..., :half]], -1) * sin


def _sparse_attention(h, layer, cfg: TransformerConfig, qk_scales):
    """Learned sparse attention on normed hidden states h (B, S, D) -> (the
    mixer's output (B, S, D), the indexer's KL loss, a scalar). q, k, v as
    every attention layer's (`_split_heads`: the norm a head, the rotary
    pass). The lightning indexer reads h with its gradient stopped, in
    float32 at the highest precision, as a router does (a rounded score moves
    the last chosen key as a rounded router moves the last chosen expert): qI
    = h W_qI as (S, Hi, di), kI = LN(h W_kI), both rotated over all di
    features at `rope_theta`, w = h W_w / sqrt(Hi di), I[t, s] = sum_j w[t, j]
    relu(qI[t, j] . kI[s]). Each query's `sparse_index[2]` best-scored keys
    at or before it are its choice (all of them where it has no more), one
    choice for all heads; the softmax core runs over the chosen keys; the
    indexer's loss is the KL divergence of softmax over the chosen keys of I
    from the head-mean of the core's probabilities there, a constant. The
    cross-entropy's gradient reaches q, k, v through the chosen keys and no
    leaf of the indexer; the KL's reaches the indexer's five leaves and
    nothing else. On the flash core's setting (`attn_core` "flash") the five
    pieces are `ops.sparse_attention`'s kernels (four at `flash_blocks`, the
    choice at a block of whole rows of its own), on "dense" its plain forms.
    Scopes `attn_proj` (the four projections, with `qk_norm` and `rope`
    inside), `dsa_index`, `dsa_select`, `attn_sparse` > `attn_core` and
    `dsa_kl`."""
    from kungfu_tpu.ops import sparse_attention as dsa

    dt = cfg.dtype
    B, S, _ = h.shape
    kernels = cfg.attn_core == "flash"
    how = (*cfg.flash_blocks, cfg.flash_interpret)
    with jax.named_scope("attn_proj"):
        q, k, v, _ = _split_heads(
            h, tuple(layer[w].astype(dt) for w in ("wq", "wk", "wv")), cfg,
            qk_scales)
    scores, chosen = _sparse_choice(h, layer, cfg)
    with jax.named_scope("attn_sparse"), jax.named_scope("attn_core"):
        ctx, lse = (dsa.sparse_attention(q, k, v, chosen, None, *how) if kernels
                    else dsa.plain_sparse_attention(q, k, v, chosen))
    with jax.named_scope("dsa_kl"):
        p, entropy = (dsa.head_mean_probs(q, k, lse, chosen, None, *how)
                      if kernels else
                      dsa.plain_head_mean_probs(q, k, lse, chosen))
        kl = dsa.indexer_kl(scores, chosen, p, entropy)
    with jax.named_scope("attn_proj"):
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, -1)
        return ctx @ layer["wo"].astype(dt), kl


def _sparse_choice(h, layer, cfg: TransformerConfig):
    """The lightning indexer on normed hidden states h (B, S, D) -> (its
    scores I (B, S, S) float32, defined at s <= t, and the choice (B, S, S)
    int8): `_sparse_attention`'s first half, scopes `dsa_index` and
    `dsa_select`."""
    from kungfu_tpu.ops import sparse_attention as dsa

    f32 = jnp.float32
    B, S, _ = h.shape
    Hi, di, keys = cfg.sparse_index
    kernels = cfg.attn_core == "flash"
    how = (*cfg.flash_blocks, cfg.flash_interpret)
    with jax.named_scope("dsa_index"):
        ub = jax.lax.stop_gradient(h).astype(f32)

        def projected(w):
            return jnp.dot(ub, layer[w].astype(f32),
                           precision=jax.lax.Precision.HIGHEST)

        cos, sin = _rotary_tables(S, di, cfg.rope_theta, 1.0, ())
        qI = _rotate_half(projected("index_wq").reshape(B, S, Hi, di),
                          cos[:, None], sin[:, None])
        kI = _rotate_half(_layer_norm(
            projected("index_wk"), layer["index_ln_scale"],
            layer["index_ln_bias"], cfg.norm_eps), cos, sin)
        w = projected("index_w") * (Hi ** -0.5 * di ** -0.5)
        scores = (dsa.index_scores(qI, kI, w, *how) if kernels
                  else dsa.plain_index_scores(qI, kI, w))
    with jax.named_scope("dsa_select"):
        # Handed on through its bits, a bit a pair under the name
        # `dsa_chosen` (8.4 MB a layer of 8,192 positions): a layer that is
        # run again keeps them (`_layer_again`) and makes the scores again,
        # which the indexer's loss reads, but not the choice, whose
        # counting passes then run once a step and not twice.
        chosen = (dsa.select(scores, keys, cfg.flash_interpret) if kernels
                  else dsa.plain_select(scores, keys))
        packed = checkpoint_name(
            jnp.packbits(chosen.astype(jnp.uint8), axis=-1), "dsa_chosen")
        return scores, jnp.unpackbits(packed, axis=-1, count=S).astype(jnp.int8)


def _latent_attention(h, layer, cfg: TransformerConfig, core=None):
    """Latent attention (MLA) on normed hidden states h (B, S, D) -> (B, S,
    D). c_q = norm(h W_q_down) and a head's [q_nope | q_rope] = c_q W_q_up;
    [c_kv | k_r] = h W_kv_down, c_kv normed, and a head's [k_nope | v] =
    c_kv W_kv_up; q = [q_nope | rot(q_rope)] and every head's k = [its
    k_nope | rot(k_r)], the one rotated key of all heads; the causal core
    the configuration names over heads of nope + rope features, at the scale
    1 / sqrt(nope + rope); W_o. Training lays k and v out a head, as the
    published implementations do (absorbing W_kv_up into q is a decode
    device). Inside, a head's rotated features stand first: the same
    permutation of q's and k's features, made on W_q_up's columns and where
    k is put together, leaves every q . k as it is, and puts the rotated
    features where the one rotary pass that also lays a projection's output
    out a head expects them (`_turned`). Scopes `mla_down`, `mla_norm`,
    `mla_up` (the up-projections and what lays k out a head), `rope`,
    `attn_latent` > `attn_core`."""
    dt, eps = cfg.dtype, cfg.norm_eps
    rq, rkv, nope, rope, value = cfg.latent_dims
    H, hd = cfg.n_heads, nope + rope
    B, S, _ = h.shape
    with jax.named_scope("mla_down"):
        c_q = h @ layer["w_q_down"].astype(dt)
        c_kv = h @ layer["w_kv_down"].astype(dt)
        c_kv, k_r = c_kv[..., :rkv], c_kv[..., rkv:]
    with jax.named_scope("mla_norm"):
        c_q = _rmsnorm(c_q, _scale(layer["q_latent_norm"], cfg), eps)
        c_kv = _rmsnorm(c_kv, _scale(layer["kv_latent_norm"], cfg), eps)
    with jax.named_scope("mla_up"):
        w_q = layer["w_q_up"].astype(dt).reshape(rq, H, hd)
        w_q = jnp.concatenate([w_q[..., nope:], w_q[..., :nope]], axis=-1)
        q = c_q @ w_q.reshape(rq, H * hd)
        w_kv = layer["w_kv_up"].astype(dt).reshape(rkv, H, nope + value)
        k_nope = c_kv @ w_kv[..., :nope].reshape(rkv, H * nope)
        v = c_kv @ w_kv[..., nope:].reshape(rkv, H * value)
        k = jnp.concatenate(
            [jnp.broadcast_to(k_r[:, :, None, :], (B, S, H, rope)),
             k_nope.reshape(B, S, H, nope)], axis=-1)
        v = v.reshape(B, S, H, value).transpose(0, 2, 1, 3)
    with jax.named_scope("rope"):
        q, k = _rope(q.reshape(B, S, H, hd).transpose(0, 2, 1, 3),
                     k.transpose(0, 2, 1, 3), cfg.rope_theta, rope / hd, ())
    with jax.named_scope("attn_latent"), jax.named_scope("attn_core"):
        ctx = (core or attention_core_of(cfg))(q, k, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, H * value)
    return ctx @ layer["wo"].astype(dt)


def _core_kind_scope(cfg: TransformerConfig):
    """`attn_window` or `attn_full` around the core where a model has both
    kinds of layer to tell apart (a window anywhere in it, or split
    projections); nothing more around the one core every other
    configuration runs."""
    if cfg.window:
        return jax.named_scope("attn_window")
    if cfg.split_qkv:
        return jax.named_scope("attn_full")
    return contextlib.nullcontext()


def _l2_normed(t, scale: float, dtype):
    """t / sqrt(|t|^2 + 1e-6) * scale over the last axis, in float32."""
    t = t.astype(jnp.float32)
    norm = jax.lax.rsqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
    return (t * (norm * scale)).astype(dtype)


def _gated_norm(o, scale, z, eps):
    """rms(o) * scale * silu(z) over the last axis (a head), in float32, the
    result in o's type. No checkpoint of its own, nor `_l2_normed`: the
    block of heads they stand in is run again whole (`_delta_heads`)."""
    o32 = o.astype(jnp.float32)
    var = jnp.mean(jnp.square(o32), axis=-1, keepdims=True)
    y = o32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return (y * jax.nn.silu(z.astype(jnp.float32))).astype(o.dtype)


# value heads a block of the gated delta mixer (`_gated_delta_mixer`)
DELTA_HEAD_BLOCK = 8


@functools.partial(_recompute, static_argnums=(3,))
def _delta_heads(h, part, norm_scale, cfg: TransformerConfig):
    """A block of the Gated DeltaNet mixer's key heads with their value
    heads, from normed hidden states h (B, S, D) to the block's part of the
    mixer's output (B, S, D); `part` = the block's columns of W_qkvz, W_ba
    and the taps, its A_log and dt_bias, its rows of W_o. Keeps its
    arguments and runs again in the backward pass."""
    from kungfu_tpu.ops.gated_delta import causal_conv, gated_delta_rule

    w_qkvz, w_ba, conv_w, A_log, dt_bias, wo = part
    dt, f32 = cfg.dtype, jnp.float32
    B, S, _ = h.shape
    d = cfg.delta_heads[2]
    r = cfg.delta_heads[1] // cfg.delta_heads[0]
    kb = A_log.shape[0] // r  # key heads in this block
    with jax.named_scope("gdn_proj"):
        qkvz = (h @ w_qkvz.astype(dt)).reshape(B, S, kb, (2 + 2 * r) * d)
        ba = jnp.dot(h.astype(f32), w_ba.astype(f32),
                     precision=jax.lax.Precision.HIGHEST).reshape(B, S, kb, 2 * r)
        b, a = (t.reshape(B, S, kb * r).transpose(0, 2, 1)
                for t in (ba[..., :r], ba[..., r:]))
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(A_log.astype(f32))[:, None] * jax.nn.softplus(
            a + dt_bias.astype(f32)[:, None])
    with jax.named_scope("gdn_conv"):
        qkv = qkvz[..., :(2 + r) * d].reshape(B, S, kb * (2 + r) * d)
        qkv = jax.nn.silu(causal_conv(qkv, conv_w)).reshape(B, S, kb, (2 + r) * d)
        q = _l2_normed(qkv[..., :d], d ** -0.5, dt).transpose(0, 2, 1, 3)
        k = _l2_normed(qkv[..., d:2 * d], 1.0, dt).transpose(0, 2, 1, 3)
        v = qkv[..., 2 * d:].reshape(B, S, kb * r, d).transpose(0, 2, 1, 3)
        if r > 1:  # value head j reads key head j // r
            q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)
    with jax.named_scope("gdn_core"):
        o = gated_delta_rule(q, k, v, g, beta)
    with jax.named_scope("gdn_norm"):
        z = qkvz[..., (2 + r) * d:].reshape(B, S, kb * r, d)
        y = _gated_norm(o.transpose(0, 2, 1, 3), norm_scale, z, cfg.norm_eps)
    with jax.named_scope("gdn_proj"):
        return y.reshape(B, S, kb * r * d) @ wo.astype(dt)


def _gated_delta_mixer(h, layer, cfg: TransformerConfig):
    """The Gated DeltaNet mixer on normed hidden states h (B, S, D), Hk key
    heads and Hv = r Hk value heads of one size d. W_qkvz's columns lie a
    key head at a time, as the published layout has them: its q, its k, its
    r value heads' v and their z; W_ba's likewise, its r b and r a; the
    taps' its q, k and v channels. [q | k | v] go through the causal
    convolution and a silu; q and k are normalised a head (q over sqrt(d)
    besides); beta = sigmoid(b) and the log decay g = -exp(A_log)
    softplus(a + dt_bias), a number a value head and position, are float32
    from a float32 projection as the router's is; the gated delta rule
    (`ops.gated_delta`); an RMSNorm a head times silu(z); W_o. The heads are
    taken a block of `DELTA_HEAD_BLOCK` value heads at a time, one after
    another, each block run again in the backward pass (`_delta_heads`):
    the rule's kernels hold a chunk in VMEM (PR 37; XLA's temporaries were
    0.15 GB a head), but a block still keeps q, k, v, z and the chunks'
    states, and without the blocks' checkpoint the step does not fit the
    chip (ROADMAP S17). The result carries the name `gdn_mix`, which a layer
    that is run again keeps (`_layer_again`): the second run of such a layer
    has no reader for the blocks, so the mixer's forward runs twice a step,
    in the forward pass and once for each block's gradients, not three
    times; the identity anywhere else. Scopes `gdn_proj`, `gdn_conv`,
    `gdn_core`, `gdn_norm`."""
    Hk, Hv, _ = cfg.delta_heads
    r = Hv // Hk
    kb = max(b for b in range(1, Hk + 1)
             if Hk % b == 0 and b * r <= max(DELTA_HEAD_BLOCK, r))

    def blocks(w, axis):
        """`axis`, a key head at a time, as (blocks, ..., a block's, ...)."""
        shape = w.shape[:axis] + (Hk // kb, -1) + w.shape[axis + 1:]
        return jnp.moveaxis(w.reshape(shape), axis, 0)

    parts = (blocks(layer["w_qkvz"], 1), blocks(layer["w_ba"], 1),
             blocks(layer["conv_w"], 1), blocks(layer["A_log"], 0),
             blocks(layer["dt_bias"], 0), blocks(layer["wo"], 0))

    def one(out, part):
        return out + _delta_heads(h, part, layer["gdn_norm_scale"], cfg
                                  ).astype(jnp.float32), None

    out, _ = jax.lax.scan(one, jnp.zeros(h.shape, jnp.float32), parts)
    return checkpoint_name(out.astype(h.dtype), "gdn_mix")


def _mamba2_mixer(h, layer, cfg: TransformerConfig, segments=(), marks=()):
    """The Mamba-2 mixer on normed hidden states h (B, S, D): H heads of P
    features, a state of N a feature, G groups of H / G heads that share B
    and C (`ssm_dims`). [z | x B C | dt] = h W_in (H P + (H P + 2 G N) + H
    columns); the step Delta = softplus(dt + dt_bias) and the log decay g =
    Delta A, A = -exp(A_log), a number a head and position, float32 from a
    float32 projection as the router's is; [x | B | C] through the causal
    convolution with its bias and a silu, and v = Delta x, one kernel each
    way (`ops.ssm_conv`: it reads the projection's columns from x on where
    the matmul left them, writes [x | B | C] once and v once in the layout
    the scan reads, float32 between, and keeps its inputs alone); the
    state-space recurrence (`ops.ssm_scan`) with q = C, k = B (a group's,
    never repeated a head) and that v; + D x, the gate silu(z) and then an
    RMSNorm over each group's features, one kernel each way
    (`ops.gated_norm`: it reads the scan's output as the scan lays it out, x
    and z as the first H P columns of the convolution's and the projection's
    outputs, writes y once, and keeps those inputs alone); W_out. Both ops
    are the same kept or run again. `segments`, (the documents' numbers (B,
    S),) of packed rows, go to the convolution and to the scan, `marks`
    (their `ssm_conv.document_marks`,) to the convolution's kernels, and
    nothing else of the mixer looks beyond its own position. Scopes
    `ssm_proj`, `ssm_conv`, `ssm_core`, `ssm_norm`."""
    from kungfu_tpu.ops import gated_norm
    from kungfu_tpu.ops.ssm_conv import ssm_conv
    from kungfu_tpu.ops.ssm_scan import CHUNK, ssm_scan

    H, hp, N, G = cfg.ssm_dims
    inner, bc = H * hp, G * N
    dt, f32 = cfg.dtype, jnp.float32
    B, S, _ = h.shape
    w_in = layer["w_ssm_in"]
    with jax.named_scope("ssm_proj"):
        zxbc = h @ w_in[:, :2 * inner + 2 * bc].astype(dt)  # z its first columns
        step = jnp.dot(h.astype(f32), w_in[:, 2 * inner + 2 * bc:].astype(f32),
                       precision=jax.lax.Precision.HIGHEST)  # (B, S, H)
    with jax.named_scope("ssm_conv"):
        delta = jax.nn.softplus(step + layer["dt_bias"].astype(f32))
        g = (delta * -jnp.exp(layer["A_log"].astype(f32))).transpose(0, 2, 1)
        xbc, v = ssm_conv(zxbc, layer["conv_w"], layer["conv_b"], delta,
                          *segments, *marks)
        b, c = (xbc[..., at:at + bc].reshape(B, S, G, N).transpose(0, 2, 1, 3)
                for at in (inner, inner + bc))
    with jax.named_scope("ssm_core"):
        # the published chunk, or the largest power of two under it that
        # divides a shorter sequence: the result does not depend on it
        o = ssm_scan(c, b, v, g, math.gcd(S, CHUNK), *segments)  # (B, H, S, hp)
    with jax.named_scope("ssm_norm"):
        y = gated_norm.gated_norm(o, xbc, zxbc, layer["D_skip"],
                                  layer["ssm_norm_scale"], G, cfg.norm_eps)
    with jax.named_scope("ssm_proj"):
        return y @ layer["wo"].astype(dt)


def _short_conv_mixer(h, layer, cfg: TransformerConfig, segments=()):
    """LFM2's gated short convolution on normed hidden states h (B, S, D):
    [B | C | x] = h W_in, three equal thirds in that order; y = (C * conv(B *
    x)) W_out with conv a causal depthwise convolution of `conv_taps` taps a
    channel, c_t = sum_i k_i z_{t-(K-1)+i}, zeros before the row's first
    position. No activation, no bias, no norm and no state. The gates and
    the taps are one op (`ops.short_conv`) that reads the projection's
    output once and keeps it, the taps and the segments alone, so the mixer
    is the same kept or run again. `segments`, (the documents' numbers (B,
    S),) of packed rows, go to the op: a tap that would reach into another
    document reads zero. Scopes `sconv_proj`, `sconv_core`."""
    from kungfu_tpu.ops.short_conv import short_conv

    dt = cfg.dtype
    with jax.named_scope("sconv_proj"):
        bcx = h @ layer["conv_in"].astype(dt)
    with jax.named_scope("sconv_core"):
        y = short_conv(bcx, layer["conv_w"], *segments)
    with jax.named_scope("sconv_proj"):
        return y @ layer["conv_out"].astype(dt)


def _scale(w, cfg: TransformerConfig):
    """A norm's scale from its weight: the weight, or 1 + it."""
    return 1.0 + w if cfg.norm_offset else w


def _expert_layer(h, layer, cfg: TransformerConfig):
    """The expert layer on normed tokens h (T, D) -> (y (T, D), aux): the
    routed experts held here through `ops.moe.moe_ffn`, and the shared
    expert where the configuration has one, behind its sigmoid gate where it
    has that; both gated silu or two-matrix relu^2 (`expert_act`)."""
    from kungfu_tpu.ops.moe import (moe_ffn, raw_gates, relu2_experts,
                                    renormalised_gates, scaled, swiglu_experts)

    dt = cfg.dtype
    gates = raw_gates if cfg.gates == "raw" else renormalised_gates
    gated = cfg.expert_act == "swiglu"
    y, aux = moe_ffn(
        h, layer["router"],
        tuple(layer[w] for w in (("w_gate", "w_up", "w_down") if gated
                                 else ("w_up", "w_down"))),
        top_k=cfg.top_k, gates=scaled(gates, cfg.routed_scale),
        expert_fn=swiglu_experts if gated else relu2_experts,
        held=cfg.experts_held or None, scores=cfg.router_scores,
        bias=layer["router_bias"] if cfg.router_bias else None)
    if cfg.shared_ff:
        with jax.named_scope("moe_shared"):
            if gated:
                shared = _silu_gate_out(h @ layer["shared_gate"].astype(dt),
                                        h @ layer["shared_up"].astype(dt),
                                        layer["shared_down"].astype(dt))
            else:
                shared = _relu2_out(h @ layer["shared_up"].astype(dt),
                                    layer["shared_down"].astype(dt))
            if cfg.shared_gate:
                shared = shared * jax.nn.sigmoid(
                    (h @ layer["w_shared_gate"].astype(dt)
                     ).astype(jnp.float32)).astype(dt)
            y = y + shared
    return y, aux


def _behind(y, layer, norm: str, cfg: TransformerConfig):
    """A branch's output y behind its second norm where the configuration
    has one (`post_norms`; scope `post_norm`), else as it is."""
    if not cfg.post_norms:
        return y
    with jax.named_scope("post_norm"):
        return _rmsnorm(y, _scale(layer[norm], cfg), cfg.norm_eps)


def _taken(x, y, layer, norm: str, cfg: TransformerConfig):
    """The residual stream x with a branch's output y in it: y behind its
    second norm where the configuration has one (`_behind`), times
    `residual_multiplier` where that is not 1."""
    y = _behind(y, layer, norm, cfg)
    return x + (y if cfg.residual_multiplier == 1.0
                else y * cfg.residual_multiplier)


def _layer(x, layer, cfg: TransformerConfig, core=None, segments=()):
    """One layer -> (x, aux): a mixer and a feed-forward, each a residual
    branch behind its own norm, or one of the two alone; with `post_norms`
    the branch's output goes through a second norm before the residual takes
    it, and `residual_multiplier` scales what it takes. aux is the expert
    layer's `ops.moe.MoeAux` (router losses and token-choices per expert),
    None of any other. `segments`: (the documents' numbers (B, S),) of
    packed rows, for the mixer, with a Mamba-2 convolution's marks behind
    them where `_hidden` made them; () where a row is one document."""
    dt, eps = cfg.dtype, cfg.norm_eps
    segments, marks = segments[:1], segments[1:]
    index_kl = None
    if cfg.mixer == "none":
        pass
    elif cfg.mixer == "mamba2":
        with jax.named_scope("ssm"):
            h = _rmsnorm(x, _scale(layer["ln1_scale"], cfg), eps)
            x = _taken(x, _mamba2_mixer(h, layer, cfg, segments, marks), layer,
                       "ln1_post_scale", cfg)
    elif cfg.mixer == "short_conv":
        with jax.named_scope("sconv"):
            h = _rmsnorm(x, _scale(layer["ln1_scale"], cfg), eps)
            x = _taken(x, _short_conv_mixer(h, layer, cfg, segments), layer,
                       "ln1_post_scale", cfg)
    elif cfg.mixer == "gated_delta":
        with jax.named_scope("gdn"):
            h = _rmsnorm(x, _scale(layer["ln1_scale"], cfg), eps)
            x = _taken(x, _gated_delta_mixer(h, layer, cfg), layer,
                       "ln1_post_scale", cfg)
    elif cfg.mixer == "latent":
        with jax.named_scope("attn"):
            h = _rmsnorm(x, _scale(layer["ln1_scale"], cfg), eps)
            x = _taken(x, _latent_attention(h, layer, cfg, core=core), layer,
                       "ln1_post_scale", cfg)
    else:
        with jax.named_scope("attn"):
            scales = ((_scale(layer["q_norm_scale"], cfg),
                       _scale(layer["k_norm_scale"], cfg))
                      if cfg.qk_norm else None)
            h = _rmsnorm(x, _scale(layer["ln1_scale"], cfg), eps)
            if cfg.sparse_index:
                y, index_kl = _sparse_attention(h, layer, cfg, scales)
                x = _taken(x, y, layer, "ln1_post_scale", cfg)
            else:
                wqkv = (tuple(layer[w].astype(dt) for w in ("wq", "wk", "wv"))
                        if cfg.split_qkv else layer["wqkv"].astype(dt))
                x = _taken(
                    x, _attention(h, wqkv, layer["wo"].astype(dt),
                                  cfg, core=core, qk_scales=scales,
                                  w_head_gate=(layer["w_head_gate"].astype(dt)
                                               if cfg.head_gate else None),
                                  segments=segments),
                    layer, "ln1_post_scale", cfg)
    x, aux = _feed_forward(x, layer, cfg)
    # an indexer's record beside the expert layer's
    return x, aux if index_kl is None else LayerAux(aux, index_kl)


def _feed_forward(x, layer, cfg: TransformerConfig):
    """A layer's second branch on the residual stream x -> (x, the expert
    layer's aux or None)."""
    dt, eps = cfg.dtype, cfg.norm_eps
    if cfg.ffn == "none":
        return x, None
    if cfg.ffn == "moe":
        with jax.named_scope("moe"):
            B, S, D = x.shape
            h = _rmsnorm(x, _scale(layer["ln2_scale"], cfg), eps).reshape(B * S, D)
            y, aux = _expert_layer(h, layer, cfg)
            return _taken(x, y.reshape(B, S, D), layer, "ln2_post_scale",
                          cfg), aux
    with jax.named_scope("ffn"):
        h = _rmsnorm(x, _scale(layer["ln2_scale"], cfg), eps)
        if cfg.ffn == "swiglu":
            y = _silu_gate_out(h @ layer["w_gate"].astype(dt),
                               h @ layer["w_up"].astype(dt),
                               layer["w_down"].astype(dt))
        else:
            y = _gelu_out(h @ layer["w_in"].astype(dt),
                          layer["w_out"].astype(dt))
        return _taken(x, y, layer, "ln2_post_scale", cfg), None


# `layer_remat`: the scan keeps the layer's input and, of what the layer
# computes, the flash core's output and row sums, whatever the call (0.15 GB
# a layer of 72 heads of 128 at 8,192 positions, 0.085 GB a layer of 20
# heads of 256), and a Gated DeltaNet mixer's output (`gdn_mix`: 0.067 GB a
# layer of 16,384 positions of 2,048): the projections, the rotation and the
# feed-forward are run again in the backward pass, the forward kernel and
# the DeltaNet mixer's head blocks are not (the blocks run their forward
# once more for their own gradients, `_delta_heads`: twice a step in all).
# Of a short-convolution mixer nothing is kept: its op's forward kernel is one
# pass over the projection's output (0.2 ms a layer of 8,192 positions of
# 2,048 channels) and runs again with the projection that feeds it. Of a
# learned sparse index the choice is kept as a bit a pair (`dsa_chosen`, 8.4 MB
# a layer of 8,192 positions) beside its core's output and row sums: the
# indexer's scores are made again (268 MB a layer, read by the indexer's
# loss), the search for each query's keys is not.
# Under a loop every application of a layer keeps its own: the Ouro cell's 32
# applications (8 layers x 4 loop steps) of 16 heads of 128 at 4,096 positions
# keep 2 x 16.8 MB each and the row sums, 1.08 GB a step beside the 0.82 GB of
# bfloat16 copies of the 8 layers' weights: kept activations to weights four
# times any other cell's
_layer_again = jax.checkpoint(
    _layer, static_argnums=(2,), prevent_cse=False,
    policy=jax.checkpoint_policies.save_only_these_names(
        "flash_out", "flash_lse", "gdn_mix", "dsa_chosen"))


def _block(x, layer, cfg: TransformerConfig, core=None):
    """One layer's hidden states alone, for the paths that have no place
    for an expert layer's auxiliary losses (pipeline, ring, a plugged
    core). A short-convolution mixer is not built there: a sequence shard
    would want the last taps' rows of the shard before it, and a pipeline
    stage runs one kind of layer."""
    if cfg.mixer == "short_conv":
        raise NotImplementedError(
            "mixer 'short_conv' runs on the normal path (`transformer_loss`): "
            "the ring path would have to hand a shard the rows before it, "
            "and neither it nor the pipeline path is built for the mixer")
    if cfg.sparse_index:
        raise NotImplementedError(
            "sparse_index runs on the normal path (`transformer_loss`): the "
            "ring and pipeline paths have no place for the indexer's loss, "
            "and a sequence shard's choice would range over other shards' keys")
    return _layer(x, layer, cfg, core=core)[0]


def _head_logits(params, x, cfg: TransformerConfig, normed: bool = False):
    """Final norm and the LM head, tied to the embedding or `lm_head` of its
    own, in float32; `normed`: x has been through the final norm already (a
    loop step's state), and the head is all there is to do."""
    h = x if normed else _rmsnorm(x, _scale(params["ln_f_scale"], cfg),
                                  cfg.norm_eps)
    head = params["embed"] if cfg.tied_head else params["lm_head"]
    h = h.astype(jnp.float32)
    if cfg.logits_scaling != 1.0:  # the logits over it: on the narrow side
        h = h / cfg.logits_scaling
    return h @ head.astype(jnp.float32).T


def lm_head_loss(params, x, targets, cfg: TransformerConfig):
    """Final norm + LM head + next-token cross-entropy on hidden states `x`
    (..., S, D). The ONE implementation shared by the dense, ring
    (sequence-parallel) and pipeline paths — a loss change (label
    smoothing, z-loss, dtype policy) lands everywhere at once. A new term
    of the loss goes into `_xent_fwd` and its derivative into `_xent_bwd`,
    both as functions of the logits and their log-sum-exp: a `log_softmax`
    beside them writes the second (rows, vocabulary) array back."""
    with jax.named_scope("head_loss"):
        return _xent(_head_logits(params, x, cfg), targets)


@jax.custom_vjp
def _xent(logits, targets):
    """mean(logsumexp(logits) - logits[target]) over (..., V) logits and
    (...) targets. A `custom_vjp` to say which one array of the logits'
    size the backward pass reads: the logits themselves, which the head's
    matmul writes anyway, with the per-row log-sum-exp beside them.
    Autodiff of `log_softmax` keeps the log-probabilities and that of
    `logsumexp` keeps exp(x - max), each a second such array written and
    read every step (3.05 ms of bert_base's, PERF.md, PR 31)."""
    return _xent_fwd(logits, targets)[0]


def _xent_rows_fwd(logits, targets):
    """`_xent` before its mean, a number a row, and its residuals."""
    lse = jax.nn.logsumexp(logits, axis=-1)  # shifted by the row maximum
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return lse - picked, (logits, lse, targets)


def _xent_fwd(logits, targets):
    rows, res = _xent_rows_fwd(logits, targets)
    return jnp.mean(rows), res


def _dlogits(res, weight):
    """(softmax - onehot) * weight(), a scalar or a number a row (..., 1),
    elementwise in the residuals, the one-hot as a comparison with an iota
    and not a scatter: XLA fuses it into the operands of the two backward
    matmuls. (`weight` is called where the product wants it, so that
    `_xent`'s backward pass traces as it always did.)"""
    logits, lse, targets = res
    hot = jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, logits.ndim - 1) == targets[..., None]
    dlogits = (jnp.exp(logits - lse[..., None]) - hot) * weight()
    return dlogits.astype(logits.dtype), None


def _xent_bwd(res, g):
    """(softmax - onehot) * g / rows."""
    return _dlogits(res, lambda: g / res[1].size)


_xent.defvjp(_xent_fwd, _xent_bwd)


def _embed(params, tokens, cfg: TransformerConfig):
    S = tokens.shape[1]
    if S > cfg.max_seq and cfg.positions == "rope":
        raise ValueError(f"sequence {S} exceeds max_seq {cfg.max_seq}")
    dt = cfg.dtype
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        if cfg.positions == "learned":
            x = x + params["pos_embed"].astype(dt)[:S]
        return x


def _segments(tokens, cfg: TransformerConfig):
    """(the documents' numbers (B, S) int32,) of packed rows, or () where a
    row is one document (`end_of_document` None): position t is of the
    document that its id ends or continues, so the number rises by one
    behind every `end_of_document` id, and a row's head is a document of its
    own, number 0. What every mixer is handed; scope `segments`."""
    if cfg.end_of_document is None:
        return ()
    with jax.named_scope("segments"):
        behind_an_end = jnp.pad(tokens[:, :-1] == cfg.end_of_document,
                                ((0, 0), (1, 0)))
        return (jnp.cumsum(behind_an_end.astype(jnp.int32), axis=1),)


def _loop_step_end(u, params, cfg: TransformerConfig, each):
    """The end of a loop step on the stacks' output u: the model's final
    norm (scope `loop_norm`) -> (what the next loop step reads, what the
    loop hands back of this one), both of the normed state."""
    with jax.named_scope("loop_norm"):
        x = _rmsnorm(u, _scale(params["ln_f_scale"], cfg), cfg.norm_eps)
    return x, each(x) if each else x


def _hidden(params, tokens, cfg: TransformerConfig, each=None):
    """-> (final hidden states, the expert layers' stacked aux or None),
    one scan for each stack of layers of one kind. Under plain S-SGD on
    several chips a layer's gradients are averaged in the iteration of the
    backward scan that produces them (`ops.collective.reduce_in_backward`,
    the identity otherwise).

    Under a loop (`loop_steps` T > 1) the scans are the body of an outer
    `lax.scan` of T iterations over the same stacked trees, with the final
    norm at the end of every loop step (scope `loop_norm`): the normed state
    is what the next loop step reads, and the T of them, (T, B, S, D), are
    handed back in the place of the one un-normed state, or, where the
    caller gives `each`, what `each(normed state)` makes of them, computed
    inside the loop step and stacked (the loss's head passes: one after
    another, each beside the backward pass of its own loop step, and no
    schedule of XLA's choosing with four logits arrays alive). A shared leaf's
    gradient is the sum over its T uses, which the outer scan's backward
    pass carries and adds to, so there the stacks go through
    `reduce_in_backward` whole and once, before the loop: each leaf is
    averaged once a step, after the sum. (T scans in a Python loop leave the
    order of the T backward scans and their head passes to XLA: the Ouro
    cell's step then wants 19.3 GB of the chip's 16.9 and the outer scan
    15.2, `benchmark/aot_check.py`, PR 48.)"""
    x = _embed(params, tokens, cfg)
    # the documents of packed rows: constants of every layer scan
    packed = _segments(tokens, cfg)
    if packed and any(kind.mixer == "mamba2" for kind, _ in cfg.stacks):
        from kungfu_tpu.ops.ssm_conv import document_marks

        with jax.named_scope("segments"):  # once a step, for every such layer
            packed += (document_marks(*packed),)
    packed = (None, packed) if packed else ()  # no core plugged, then they
    stacks = params["layers"] if cfg.layer_kinds else (params["layers"],)
    looped = cfg.loop_steps > 1
    if looped:
        stacks = collective.reduce_in_backward(stacks)

    def run_stacks(x):
        auxes = []
        for (kind, _), stacked in zip(cfg.stacks, stacks, strict=True):

            run = _layer_again if kind.layer_remat else _layer

            def body(x, layer, kind=kind, stacked=stacked, run=run):
                if not looped:
                    layer = collective.reduce_in_backward(layer, of=stacked)
                return run(x, layer, kind, *packed)

            x, aux = jax.lax.scan(body, x, stacked)
            if aux is not None:
                auxes.append(aux)
        return x, auxes

    if looped:
        def loop_step(x, _):
            return _loop_step_end(run_stacks(x)[0], params, cfg, each)

        return jax.lax.scan(loop_step, x, None, length=cfg.loop_steps)[1], None
    x, auxes = run_stacks(x)
    if len(auxes) > 1:  # the expert layers' aux, stack after stack
        return x, jax.tree.map(lambda *a: jnp.concatenate(a), *auxes)
    return x, auxes[0] if auxes else None


def transformer_hidden(params, tokens, cfg: TransformerConfig):
    """tokens (B, S) int32 -> final hidden states (B, S, D) pre-norm; of a
    loop, the last loop step's, which the final norm has been over."""
    x = _hidden(params, tokens, cfg)[0]
    return x[-1] if cfg.loop_steps > 1 else x


def transformer_apply(params, tokens, cfg: TransformerConfig):
    """tokens (B, S) int32 -> logits (B, S, V) in f32; of a loop those of
    its last loop step (no early exit: the published
    `early_exit_threshold` 1)."""
    return _head_logits(params, transformer_hidden(params, tokens, cfg), cfg,
                        normed=cfg.loop_steps > 1)


def _mtp_hidden(params, x, tokens_next, cfg: TransformerConfig):
    """The multi-token-prediction module on the stack's output x (B, S, D),
    before the final norm, and the tokens one further on (B, S): h'_i =
    [norm_e(E(t_{i+1})) | norm_h(x_i)] W_eh, E the model's own embedding,
    then one block of the last layer's kind -> (its output, before the
    module's final norm; the block's aux). Scope `mtp_proj`, then the
    block's own."""
    mtp, kind = params["mtp"], cfg.mtp_kind
    with jax.named_scope("mtp_proj"):
        e = _rmsnorm(_embed(params, tokens_next, cfg),
                     _scale(mtp["enorm_scale"], cfg), cfg.norm_eps)
        h = _rmsnorm(x, _scale(mtp["hnorm_scale"], cfg), cfg.norm_eps)
        h = jnp.concatenate([e, h], axis=-1) @ mtp["eh_proj"].astype(cfg.dtype)
    return (_layer_again if kind.layer_remat else _layer)(h, mtp["layer"], kind)


def _split_batch(batch, cfg: TransformerConfig):
    """-> (tokens, targets, the ids two further on (B, S) or None). batch =
    ids (B, S + 1 + mtp_depth), or (tokens, targets) where there is no
    multi-token-prediction module."""
    if isinstance(batch, (tuple, list)):
        if cfg.mtp_depth:
            raise ValueError("a multi-token-prediction module reads ids "
                             "(B, S + 2), not (tokens, targets)")
        return (*batch, None)
    S = batch.shape[1] - 1 - cfg.mtp_depth
    return batch[:, :S], batch[:, 1:S + 1], batch[:, 2:] if cfg.mtp_depth else None


def _reduced_outside_the_stacks(params):
    """`params` with the leaves outside the layer scan (the stacks' go
    through `_hidden`'s) passed through `reduce_in_backward`: under plain
    S-SGD on several chips their gradients are averaged where the backward
    pass completes them."""
    return {**collective.reduce_in_backward(
        {k: v for k, v in params.items() if k != "layers"}),
        "layers": params["layers"]}


def _losses(params, batch, cfg: TransformerConfig):
    """-> (main next-token loss, the multi-token-prediction module's loss
    or None, the expert layers' aux of the stack or None)."""
    tokens, targets, ahead = _split_batch(batch, cfg)
    params = _reduced_outside_the_stacks(params)
    x, aux = _hidden(params, tokens, cfg)
    loss = lm_head_loss(params, x, targets, cfg)
    if not cfg.mtp_depth:
        return loss, None, aux
    with jax.named_scope("mtp"):
        # position i reads t_{i+1} and predicts t_{i+2}, through the
        # module's own final norm and the model's own head
        x, _ = _mtp_hidden(params, x, targets, cfg)
        own = {**params, "ln_f_scale": params["mtp"]["ln_f_scale"]}
        return loss, lm_head_loss(own, x, ahead, cfg), aux


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _loop_step_rows(head, x, targets, cfg: TransformerConfig):
    """A loop step's head pass on its normed state x -> the cross-entropy a
    position, `_xent` before its mean: logsumexp(logits) - logits[target] of
    `_head_logits`, `head` the leaf it reads, for a loss that weighs the rows
    itself. Keeps x, the head, the targets and the rows' log-sum-exp, and
    makes the logits again in the backward pass: a float32 logits array of
    the Ouro cell is 0.81e9 bytes, and with the four of a step kept the step
    does not fit the chip (15.95 GiB of its 15.75, `benchmark/aot_check.py`,
    PR 48); the head's product once more a loop step is 0.8 of the step's 57
    TFLOP each. A `custom_vjp` of its own where `_recompute` would do, for
    the log-sum-exp it keeps: the checkpoint makes that again too, a second
    pass over the logits, and the cell's step reads 605.3 ms for 586.7
    (`head_loss_ms` 94.4 for 75.8; my chip runs, PR 48). The barrier is for a
    caller outside a scan, where XLA would merge the second product with the
    first and keep the logits."""
    return _xent_rows_fwd(_head_logits(head, x, cfg, normed=True), targets)[0]


def _loop_step_rows_fwd(head, x, targets, cfg):
    rows, (_, lse, _) = _xent_rows_fwd(
        _head_logits(head, x, cfg, normed=True), targets)
    return rows, (head, x, targets, lse)


def _loop_step_rows_bwd(cfg, res, g):
    head, x, targets, lse = res
    x, g = jax.lax.optimization_barrier((x, g))
    logits, pull = jax.vjp(
        lambda head, x: _head_logits(head, x, cfg, normed=True), head, x)
    return (*pull(_dlogits((logits, lse, targets),
                           lambda: g[..., None])[0]), None)


_loop_step_rows.defvjp(_loop_step_rows_fwd, _loop_step_rows_bwd)


def _exit_log_shares(gates):
    """The exit distribution of a loop from its gates g_1..g_{T-1}, (T - 1,
    ...) float32, as log p_1..log p_T (T, ...): lambda_t = sigmoid(g_t), p_t
    = lambda_t prod_{j<t} (1 - lambda_j), and p_T = prod_{j<T} (1 -
    lambda_j) takes what is left, so the T sum to one; in logarithms, where
    a gate far from 0 loses nothing."""
    left = jnp.cumsum(jax.nn.log_sigmoid(-gates), axis=0)  # j <= t
    before = jnp.concatenate([jnp.zeros_like(left[:1]), left[:-1]])  # j < t
    return jnp.concatenate([jax.nn.log_sigmoid(gates) + before, left[-1:]])


def _loop_losses(params, batch, cfg: TransformerConfig):
    """The parts of a loop's loss, each a float32 scalar or one a loop step
    (T,): `loss` = mean over positions of [sum_t p_t l_t - beta H(p)], l_t
    the next-token cross-entropy of loop step t's head pass (`lm_head_loss`'s
    head and `_xent`'s residuals, a pass a loop step one after another, on
    the state the final norm has been over), p the exit distribution from
    the gates g_t = x_t w_g + b_g on the same states (float32; the last loop
    step's gate is read by nothing and not computed), H(p) = -sum_t p_t log
    p_t and beta `exit_entropy_coef`: the first-stage objective of Ouro's
    paper (arXiv:2510.25741); `loop` the mean l_t, `exit_share` the mean p_t,
    `exit_entropy` the mean H. Scopes `head_loss` over the passes, and
    `exit_gate` for the gates' product, the distribution, the expected loss
    and the entropy."""
    tokens, targets, _ = _split_batch(batch, cfg)
    params = _reduced_outside_the_stacks(params)
    f32 = jnp.float32
    head = {k: params[k] for k in ("embed" if cfg.tied_head else "lm_head",)}

    def head_pass(x):
        with jax.named_scope("head_loss"):
            return x, _loop_step_rows(head, x, targets, cfg)

    (states, rows), _ = _hidden(params, tokens, cfg, each=head_pass)  # (T, B, S, ...)
    with jax.named_scope("exit_gate"):
        gates = jnp.dot(states[:-1].astype(f32),
                        params["exit_gate_w"].astype(f32),
                        precision=jax.lax.Precision.HIGHEST)[..., 0]
        logp = _exit_log_shares(gates + params["exit_gate_b"].astype(f32))
        p = jnp.exp(logp)
        entropy = -jnp.sum(p * logp, axis=0)
        expected = jnp.sum(p * rows, axis=0)
        return {"loss": jnp.mean(expected - cfg.exit_entropy_coef * entropy),
                "loop": jnp.mean(rows, axis=(1, 2)),
                "exit_share": jnp.mean(p, axis=(1, 2)),
                "exit_entropy": jnp.mean(entropy)}


def transformer_loss(params, batch, cfg: TransformerConfig):
    """Next-token cross-entropy, plus the expert layers' load-balancing and
    router z-losses (each a mean over the layers) at the configuration's
    coefficients, plus `mtp_weight` times the multi-token-prediction
    module's cross-entropy where the configuration has one (both means over
    the S positions); of a loop (`loop_steps` > 1) the expected
    cross-entropy over its exit distribution less `exit_entropy_coef` times
    that distribution's entropy (`_loop_losses`). batch = tokens (B, S+1) or
    (tokens, targets); with the module, ids (B, S+2)."""
    if cfg.loop_steps > 1:
        return _loop_losses(params, batch, cfg)["loss"]
    return transformer_loss_and_parts(params, batch, cfg)[0]


def transformer_loss_and_parts(params, batch, cfg: TransformerConfig):
    """(`transformer_loss`, `transformer_losses`) from one pass over the
    batch: what a caller differentiates with the parts as its `has_aux`
    (a comparison of the indexer's loss beside the gradients, say), so that
    the parts cost no second forward pass. Not of a loop."""
    main, mtp_loss, aux = _losses(params, batch, cfg)
    parts = {"main": main} if mtp_loss is None else {"main": main, "mtp": mtp_loss}
    loss = main
    aux, index_kl = _aux_parts(aux)
    if mtp_loss is not None:
        loss = loss + cfg.mtp_weight * mtp_loss
    if aux is not None and (cfg.router_aux_coef or cfg.router_z_coef):
        with jax.named_scope("moe"), jax.named_scope("moe_router"):
            loss = (loss + cfg.router_aux_coef * jnp.mean(aux.load_balance)
                    + cfg.router_z_coef * jnp.mean(aux.z_loss))
    if index_kl is not None:  # the sum over the layers, not their mean
        with jax.named_scope("attn"), jax.named_scope("dsa_kl"):
            parts["indexer_kl"] = jnp.sum(index_kl)
            loss = loss + cfg.indexer_loss_weight * parts["indexer_kl"]
    return loss, parts


def transformer_losses(params, batch, cfg: TransformerConfig):
    """The parts of `transformer_loss` on one batch: `main`, the next-token
    cross-entropy (of a loop, its last loop step's), and `mtp`, the
    multi-token-prediction module's, where the configuration has one, each a
    scalar; `indexer_kl`, the lightning indexers' KL loss summed over the
    layers, where it has those (`sparse_index`); of a loop also `loop`, every
    loop step's cross-entropy, and
    `exit_share`, the batch's mean exit share of each, (T,) both, and
    `exit_entropy`. Jit this beside the step, as `routing_stats`: the step
    returns their weighted sum and nothing else."""
    if cfg.loop_steps > 1:
        parts = _loop_losses(params, batch, cfg)
        return {"main": parts["loop"][-1], **{
            k: parts[k] for k in ("loop", "exit_share", "exit_entropy")}}
    return transformer_loss_and_parts(params, batch, cfg)[1]


def record_losses(losses, registry=None) -> None:
    """`transformer_losses`' numbers as gauges of `telemetry.metrics`:
    `kungfu_lm_loss` and, beside it where there is one, `kungfu_mtp_loss`
    and `kungfu_indexer_kl`;
    of a loop `kungfu_loop_loss` and `kungfu_exit_share`, a series a loop
    step (`step`, from 1), and `kungfu_exit_entropy`."""
    from kungfu_tpu.telemetry import metrics

    reg = registry or metrics.REGISTRY
    reg.gauge("kungfu_lm_loss", "next-token cross-entropy of the batch "
              "read last").set(float(losses["main"]))
    if "mtp" in losses:
        reg.gauge("kungfu_mtp_loss", "the multi-token-prediction module's "
                  "cross-entropy on the same batch").set(float(losses["mtp"]))
    if "indexer_kl" in losses:
        reg.gauge("kungfu_indexer_kl", "the lightning indexers' KL loss on the "
                  "same batch, summed over the layers").set(
                      float(losses["indexer_kl"]))
    if "loop" in losses:
        loop = reg.gauge("kungfu_loop_loss", "a loop step's next-token "
                         "cross-entropy on the same batch", ("step",))
        share = reg.gauge("kungfu_exit_share", "the batch's mean share of "
                          "the exit distribution at a loop step", ("step",))
        for t, (l, p) in enumerate(zip(np.asarray(losses["loop"]),
                                       np.asarray(losses["exit_share"])), 1):
            loop.labels(t).set(float(l))
            share.labels(t).set(float(p))
        reg.gauge("kungfu_exit_entropy", "the batch's mean entropy of the "
                  "exit distribution").set(float(losses["exit_entropy"]))


def routing_stats(params, tokens, cfg: TransformerConfig):
    """What the router did with tokens (B, S), expert layer by expert
    layer: jit this beside the step (the step returns a loss and nothing
    else). `counts` (L, experts held) token-choices computed per expert
    here, `held_rows` (L,) their sum, `dropped` (L,) of the token-choices
    that fell on an expert held here those that were not computed (0: the
    expert layer has no capacity), and `max_over_mean` (L,) the busiest
    held expert's load over the mean load of all the router's experts;
    `chosen` (L, B * S, top_k) the experts each token took; `layer` (L,)
    which of the model's layers each row is; under a selection bias
    (`router_bias`) `bias_moved` (L,), the token-choices that the bias
    changed against a choice on the scores alone. With a
    multi-token-prediction module, tokens (B, S + 1): the module reads the
    ids one further on, and where its block has an expert layer that is the
    last row, `layer` = n_layers."""
    if cfg.mtp_depth:
        tokens, tokens_next = tokens[:, :-1], tokens[:, 1:]
    x, aux = _hidden(params, tokens, cfg)
    aux = _aux_parts(aux)[0]
    kinds = [kind for kind, n in cfg.stacks for _ in range(n)]
    if cfg.mtp_depth and cfg.mtp_kind.ffn == "moe":
        own = _mtp_hidden(params, x, tokens_next, cfg)[1]
        own = jax.tree.map(lambda a: a[None], own)
        aux = own if aux is None else jax.tree.map(
            lambda *a: jnp.concatenate(a), aux, own)
        kinds.append(cfg.mtp_kind)
    if aux is None:
        raise ValueError("routing_stats: the configuration has no expert layer")
    moe = [kind for kind in kinds if kind.ffn == "moe"][0]
    choices = tokens.size * moe.top_k
    first, held = moe.experts_held or (0, moe.n_experts)
    counts = aux.counts
    asked = jnp.sum((aux.chosen >= first) & (aux.chosen < first + held),
                    axis=(1, 2))
    stats = {
        "counts": counts,
        "held_rows": jnp.sum(counts, axis=-1),
        "dropped": asked - jnp.sum(counts, axis=-1),
        "max_over_mean": jnp.max(counts, axis=-1) * (moe.n_experts / choices),
        "chosen": aux.chosen,
        "layer": jnp.asarray([i for i, kind in enumerate(kinds)
                              if kind.ffn == "moe"], jnp.int32),
    }
    if aux.bias_moved is not None:
        stats["bias_moved"] = aux.bias_moved
    return stats


def sparse_choices(params, tokens, cfg: TransformerConfig):
    """The keys every layer's lightning indexer chooses for tokens (B, S):
    (layers, B, S, S) int8, 1 where query t attends to key s. Jit this beside
    the step, as `routing_stats`; the layers one after another and not in a
    scan, which would stack nothing else this large."""
    if not cfg.sparse_index:
        raise ValueError("sparse_choices: the configuration has no "
                         "sparse_index, so every earlier key is seen")
    x = _embed(params, tokens, cfg)
    chosen = []
    for at in range(cfg.n_layers):
        layer = jax.tree.map(lambda leaf: leaf[at], params["layers"])
        h = _rmsnorm(x, _scale(layer["ln1_scale"], cfg), cfg.norm_eps)
        chosen.append(_sparse_choice(h, layer, cfg)[1])
        x = _layer(x, layer, cfg)[0]
    return jnp.stack(chosen)


def record_routing(stats, registry=None) -> None:
    """`routing_stats`' numbers as gauges of `telemetry.metrics`, a series
    a layer: `kungfu_moe_dropped_token_choices`, `kungfu_moe_max_over_mean_load`,
    `kungfu_moe_held_rows` and `kungfu_moe_held_share` (the token-choices
    computed here, and their share of all the layer's), per expert held
    `kungfu_moe_expert_token_choices`, and under a selection bias
    `kungfu_moe_bias_moved_token_choices`."""
    from kungfu_tpu.telemetry import metrics

    reg = registry or metrics.REGISTRY
    dropped = reg.gauge("kungfu_moe_dropped_token_choices",
                        "token-choices the expert layer did not compute",
                        ("layer",))
    skew = reg.gauge("kungfu_moe_max_over_mean_load",
                     "the busiest expert's token-choices over the mean",
                     ("layer",))
    load = reg.gauge("kungfu_moe_expert_token_choices",
                     "token-choices computed by one expert",
                     ("layer", "expert"))
    rows = reg.gauge("kungfu_moe_held_rows",
                     "token-choices computed by the experts held here",
                     ("layer",))
    share = reg.gauge("kungfu_moe_held_share",
                      "held rows over all the layer's token-choices",
                      ("layer",))
    moved = reg.gauge("kungfu_moe_bias_moved_token_choices",
                      "token-choices the router's selection bias changed",
                      ("layer",)) if "bias_moved" in stats else None
    choices = stats["chosen"][0].size
    for i, row in enumerate(np.asarray(stats["counts"])):
        layer = int(stats["layer"][i])
        dropped.labels(layer).set(float(stats["dropped"][i]))
        skew.labels(layer).set(float(stats["max_over_mean"][i]))
        rows.labels(layer).set(float(stats["held_rows"][i]))
        share.labels(layer).set(float(stats["held_rows"][i]) / choices)
        if moved is not None:
            moved.labels(layer).set(float(stats["bias_moved"][i]))
        for expert, n in enumerate(row):
            load.labels(layer, expert).set(float(n))


def packing_stats(tokens, cfg: TransformerConfig):
    """What tokens (B, S) of packed rows are made of, a number a row (B,):
    jit this beside the step, as `routing_stats`. `documents`, how many the
    row holds (a row's head and its tail are documents of their own);
    `shortest` and `longest`, their lengths in positions; and
    `within_document_pairs`, the share of the row's causal (query, key)
    pairs, the diagonal among them, whose two positions are of one document:
    what fraction of a full causal sweep the attention needs."""
    if cfg.end_of_document is None:
        raise ValueError("packing_stats: the configuration names no "
                         "end_of_document id, so a row is one document")
    (segments,) = _segments(tokens, cfg)
    S = tokens.shape[1]
    lengths = jax.vmap(lambda row: jnp.bincount(row, length=S))(segments)
    pairs = jnp.sum(lengths * (lengths + 1) // 2, axis=-1)
    return {
        "documents": segments[:, -1] + 1,
        "shortest": jnp.min(jnp.where(lengths > 0, lengths, S), axis=-1),
        "longest": jnp.max(lengths, axis=-1),
        "within_document_pairs": pairs / (S * (S + 1) // 2),
    }


def record_packing(stats, registry=None) -> None:
    """`packing_stats`' numbers as gauges of `telemetry.metrics` over the
    batch read last: `kungfu_packed_documents_per_row` (the mean),
    `kungfu_packed_shortest_document` and `kungfu_packed_longest_document`
    (positions, over all rows) and `kungfu_packed_within_document_pairs`
    (the share, over all rows)."""
    from kungfu_tpu.telemetry import metrics

    reg = registry or metrics.REGISTRY
    for name, text, value in (
            ("kungfu_packed_documents_per_row",
             "documents a packed row holds, the mean",
             np.mean(stats["documents"])),
            ("kungfu_packed_shortest_document",
             "positions of the shortest document", np.min(stats["shortest"])),
            ("kungfu_packed_longest_document",
             "positions of the longest document", np.max(stats["longest"])),
            ("kungfu_packed_within_document_pairs",
             "the share of causal pairs that lie within one document",
             np.mean(stats["within_document_pairs"]))):
        reg.gauge(name, text + ", of the batch read last").set(float(value))


# ---------------------------------------------------------------------------
# sequence-parallel (ring attention) path: the long-context mode. The whole
# forward runs per sequence-SHARD inside a shard_map over (dp, sp) — token
# embedding, norms and FFN are pointwise over positions, so only attention
# needs cross-shard traffic, and that traffic is the K/V ring on ICI
# (ops/ring_attention.py). Peak activation memory per chip scales with
# S/sp instead of S.
# ---------------------------------------------------------------------------


def ring_transformer_apply_shard(params, tokens, cfg: TransformerConfig,
                                 sp_axis: str, sp_size: int):
    """Per-shard forward for shard_map: tokens (B, S_local) is this
    device's sequence chunk; returns per-shard pre-norm hidden states
    (B, S_local, D) — feed them to lm_head_loss."""
    from kungfu_tpu.ops.ring_attention import ring_self_attention

    if cfg.positions != "learned" or cfg.ffn == "moe":
        raise NotImplementedError(
            "the ring path slices the learned position table a shard and "
            "has no place for an expert layer's losses; rotary positions "
            "need per-shard offsets (ROADMAP R6)")
    B, Sl = tokens.shape
    if sp_size * Sl > cfg.max_seq:
        # loud, like the dense path: dynamic_slice would otherwise CLAMP
        # the out-of-range start and silently duplicate positional rows
        raise ValueError(
            f"global sequence {sp_size * Sl} exceeds max_seq {cfg.max_seq}"
        )
    dt = cfg.dtype
    with jax.named_scope("embed"):
        idx = jax.lax.axis_index(sp_axis)
        pos = jax.lax.dynamic_slice(
            params["pos_embed"], (idx * Sl, 0), (Sl, cfg.d_model)
        )
        x = params["embed"].astype(dt)[tokens] + pos.astype(dt)

    def ring_core(q, k, v):
        return ring_self_attention(q, k, v, sp_axis, sp_size, causal=True)

    def body(x, layer):
        # the ONE block implementation, with the ring attention core
        return _block(x, layer, cfg, core=ring_core), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return x  # pre-final-norm hidden states, like transformer_hidden


def make_ring_transformer_loss(cfg: TransformerConfig, mesh,
                               sp_axis: str = "sp", dp_axis: str = "dp"):
    """Sequence-parallel causal-LM loss: batch = (tokens, targets), both
    (B, S) with B divisible by dp and S by sp. Returns loss_fn(params,
    batch) -> replicated scalar, jit/grad-compatible (shard_map inside)."""
    sp_size = mesh.shape[sp_axis]

    def shard_loss(params, batch):
        tokens, targets = batch
        x = ring_transformer_apply_shard(params, tokens, cfg, sp_axis, sp_size)
        loss = lm_head_loss(params, x, targets, cfg)
        return jax.lax.pmean(jax.lax.pmean(loss, sp_axis), dp_axis)

    return jax.shard_map(
        shard_loss,
        mesh=mesh,
        in_specs=(P(), (P(dp_axis, sp_axis), P(dp_axis, sp_axis))),
        out_specs=P(),
        check_vma=False,
    )
