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
- The layer is data (ROADMAP D1): `TransformerConfig` says what a layer is,
  and the defaults are the block the repo has always had, so `bert_base()`
  and `tiny()` mean what they meant. A layer is a token mixer and a
  feed-forward, each a residual branch behind its own norm, or one of the two
  alone (`mixer` "none", `ffn` "none": that branch's norm leaf and no other).
- The token mixer is one record of `models/mixers/` (`mixer_of(cfg)`): its
  refusals, leaves, shardings and function live in its module there
  (softmax attention and its learned sparse index, latent attention with or
  without a q latent and positions, the gated delta rule, Kimi Delta
  Attention, Mamba-2, the gated short convolution: six mixers), and this file asks
  the record wherever the mixer matters: `__post_init__`, `init_transformer`,
  `param_pspecs`, `_layer`, `_block`, `_hidden`. What the mixers and the
  layer share (the norm, the rotary pass, the cores, the checkpoint) is
  `models/blocks.py`, below both.
- What stays here: the configuration; the state and its shardings; the layer
  (`_layer`) with its feed-forward (gelu, gated silu, or routed experts
  through `ops.moe.moe_ffn` over a share of the experts the router sees, with
  a shared expert, `_expert_layer`; the router on the feed-forward's normed
  input or, `router_input` "layer", on the layer's own input ahead of the
  mixer, `_early_routing`), its second norms (`post_norms`) and the
  residual's multiplier; the stacks (`_hidden`); the heads and losses; the
  stats beside the step and their recorders; the ring path.
- Layers may differ in kind: `layer_kinds` gives each layer the fields that
  replace the configuration's own for it (mixer, heads, window, rotary rule,
  positions, feed-forward), successive layers of one kind are one stacked tree and one
  `lax.scan`, and `params["layers"]` is then the tuple of those stacks in
  the model's layer order.
- The stacks may be run more than once a forward pass (Ouro's looped model):
  `loop_steps` T > 1 makes the layer scans the body of an outer scan of T
  iterations over the one set of weights, the model's final norm at the end
  of every loop step and the normed state what the next one reads (`_hidden`,
  scope `loop_norm`); a shared leaf's gradient is the sum over its T uses, so
  under S-SGD on several chips the stacks are averaged whole and once, after
  the backward pass, and not a layer an iteration. The loss is then the
  expected cross-entropy over T head passes on the shared head under an exit
  distribution from a gate on the normed states (`exit_gate_w`,
  `exit_gate_b`), less `exit_entropy_coef` times that distribution's entropy
  (`_loop_losses`, scope `exit_gate`); each head pass is run again in the
  backward pass (`_loop_step_rows`), and `transformer_apply` gives the last
  loop step's logits.
- A multi-token-prediction module (`mtp_depth` 1: two norms, a (2D, D)
  projection, one further block, a final norm of its own) predicts the token
  after the next on the shared embedding and head, and `transformer_loss` is
  then main loss + `mtp_weight` x MTP loss from a batch of S + 2 ids. The
  routers' losses and a sparse index's (`LayerAux`) stand beside it.
- Four multipliers (Granite 4.0's): the embedding's rows times
  `embedding_multiplier`, a branch's output times `residual_multiplier`
  where the residual takes it, the attention scores times
  `attention_multiplier` in the place of 1 / sqrt(head size), the logits over
  `logits_scaling`; each 1 (or unset) leaves the program as it was.
- The residual path is one stream a position, or several (`streams` n > 1:
  manifold-constrained hyper-connections, `ops/hyper_connections.py`). With
  n streams the layer scan carries (B, S, n * D), the embedding's output
  enters as n copies (`_enter`, scope `hc_in`) and the stacks' output leaves
  as their sum (`_leave`, `hc_out`), so the heads, the losses and a
  multi-token-prediction module read (B, S, D) as ever; around each branch
  the layer makes three maps from the streams and the branch's own leaves
  (`hc1_*` the mixer's, `hc2_*` the feed-forward's), reads the branch's input
  out of the streams (`_read`: scope `hc` > `hc_maps`, `hc_read`) and writes
  the streams back with the branch's output mixed in (`_taken`: `hc_write`).
  A loop, a router that reads the layer's input, a sparse index and the ring
  and pipeline paths carry one stream and refuse several with a sentence.
  With one stream no map leaf is built and every program is what it was.
- A row may be several documents: `end_of_document` names the id that ends
  one, and the ids are the only carrier. `_segments` numbers each position's
  document (scope `segments`), and the numbers go to every mixer of the
  stack, through the layer scans and `_layer_again` alike, with what a
  mixer's record wants made of them once a step (`document_marks`). The loss
  is over every position; `packing_stats` says what a batch is made of.
  Without the id every program is what it was.

The reference has no model code (KungFu is model-agnostic); this model is
the framework's flagship workload for the BERT-config benchmark
(BASELINE.md config 3) and the long-context/sequence-parallel path.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from kungfu_tpu.models.blocks import (_layer_keys, _mixer_input, _recompute,
                                      _rmsnorm, _scale)
from kungfu_tpu.models.mixers import MIXERS, mixer_of
from kungfu_tpu.models.mixers.attention import _sparse_choice
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
    # position signal of any kind. A layer kind may say "rope" or "none" for
    # its own layers (`layer_kinds`): "learned" is the embedding's, once
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
    # mixer "kda" (Kimi Delta Attention, `ops.kda`): (heads, head size) for
    # q, k and v alike behind convolutions of `conv_taps` taps; the delta
    # rule's decay is a number a key feature, from a projection through a
    # rank of the head size, and a second such projection gates the norm
    kda_heads: Tuple = ()
    conv_taps: int = 4
    ssm_dims: Tuple = ()
    norm_offset: bool = False  # every RMSNorm's scale is 1 + w, w from 0
    # with wq, wk, wv of their own (`split_qkv`), `qk_norm` is an RMSNorm a
    # head over the head size, q's and k's scales (head size,) each.
    # q_gate: wq is twice as wide, a head's q and then its gate, and
    # sigmoid(gate), one a feature, is on the core's output
    q_gate: bool = False
    shared_gate: bool = False  # sigmoid(h @ w_shared_gate (D, 1)) on the shared expert
    # mixer "latent": (q latent rank, or 0: q = h W_q with no latent;
    # key/value latent rank; unrotated features a q/k head; rotated features
    # a q/k head; features a value head, `hd_v`, the q/k heads' size or its
    # own on either core); the rotated key is one for all heads, rotate-half
    # at rope_theta under `positions` "rope", and under "none" nothing is
    # turned: the one shared key stands beside each head's own as it is
    latent_dims: Tuple = ()
    router_scores: str = "softmax"  # or "sigmoid": each expert's own score
    # a leaf `router_bias` (n_experts,) added to the scores for the choice
    # and not for the weight; the loss is constant in it
    router_bias: bool = False
    # the routed experts' function, and the shared expert's: "swiglu", three
    # matrices, w_down (silu(w_gate x) * w_up x), "relu2", two, w_down
    # (relu(w_up x))^2, or "reglu", three, w_down (relu(w_gate x) * w_up x)
    expert_act: str = "swiglu"
    # what an expert layer's router reads: "ffn", the feed-forward's normed
    # input, the rows the experts transform; or "layer", the layer's own
    # input as it is, before the first norm and the mixer (SmallThinker's):
    # choice, gates and the order of the token-choices are then made ahead
    # of the mixer (`_early_routing`) and the experts read the normed state
    # behind it
    router_input: str = "ffn"
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
    # manifold-constrained hyper-connections (`ops.hyper_connections`,
    # arXiv:2512.24880): so many residual streams a position, mixed around
    # every branch by maps the layer computes from them (leaves `hc1_phi`,
    # `hc1_a`, `hc1_b` of the mixer's branch, `hc2_*` of the feed-forward's):
    # the Sinkhorn-Knopp passes of the streams' own map, the eps beside its
    # sums and the clamp on its logits. 1: the one residual stream, no map
    # leaf and the program it always was
    streams: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple = (-30.0, 30.0)

    def __post_init__(self):
        for field, value, known in (
                ("positions", self.positions, ("learned", "rope", "none")),
                ("ffn", self.ffn, ("gelu", "swiglu", "moe", "none")),
                ("expert_act", self.expert_act, ("swiglu", "relu2", "reglu")),
                ("router_input", self.router_input, ("ffn", "layer")),
                ("attn_core", self.attn_core, ("dense", "flash")),
                ("gates", self.gates, ("raw", "renorm")),
                ("mixer", self.mixer, (*MIXERS, "none")),
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
        if self.mixer == "none" and self.ffn == "none":
            raise ValueError("a layer is a mixer, a feed-forward or both: "
                             "mixer 'none' with ffn 'none' is no layer")
        mixer = mixer_of(self)
        if mixer is not None:  # the mixer's own refusals
            mixer.check(self)
        if self.mtp_depth not in (0, 1):
            raise ValueError(f"mtp_depth {self.mtp_depth}: one multi-token-"
                             "prediction module or none")
        if self.shared_gate and not self.shared_ff:
            raise ValueError("shared_gate gates the shared expert (shared_ff)")
        if self.expert_act == "reglu" and self.shared_ff:
            raise ValueError("expert_act 'reglu' is built for routed experts "
                             "alone: no model the repo runs has a relu-gated "
                             "shared expert (shared_ff)")
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
        if (self.window or self.kv_heads != self.n_heads
                or self.attention_multiplier) and self.attn_core != "flash" \
                and not self.sparse_index:
            raise ValueError("a window, grouped heads and a scale of the "
                             "scores' own are the flash core's (attn_core "
                             "'flash'); the dense core has none of them")
        if self.end_of_document is not None and (self.mtp_depth or not (
                mixer is None or mixer.keeps_documents_apart(self))):
            raise ValueError(
                "packed documents (end_of_document) are kept apart by the "
                "Mamba-2 mixer, the short convolution and the flash core of "
                "softmax attention: "
                f"not by mixer {self.mixer!r} on the {self.attn_core} core, "
                "nor by a multi-token-prediction module")
        if self.streams < 1:
            raise ValueError(f"streams {self.streams}: one residual stream or "
                             "more")
        if self.streams > 1 and (self.loop_steps > 1 or self.sparse_index or (
                self.router_input == "layer" and self.ffn == "moe")):
            raise ValueError(
                "residual streams (streams > 1) are built for the layer scan "
                "of the normal path: a loop's final norm a loop step, a "
                "router that reads the layer's own input ahead of the mixer "
                "(router_input 'layer') and a sparse index beside the step "
                "read one stream, and none of them carries several")
        if self.layer_kinds and len(self.layer_kinds) != self.n_layers:
            raise ValueError(f"{len(self.layer_kinds)} layer kinds for "
                             f"{self.n_layers} layers")
        for kind in self.layer_kinds:
            one = dataclasses.replace(self, layer_kinds=(), n_layers=1,
                                      **dict(kind))
            if "learned" in {self.positions, one.positions} and (
                    one.positions != self.positions):
                raise ValueError(
                    "learned positions are added to the embedding once, for "
                    "every layer: a layer kind's own `positions` is 'rope' or "
                    "'none' in a model whose own is one of the two, not "
                    f"{one.positions!r} under {self.positions!r}")

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
    def rotary(self) -> bool:
        """Whether any layer turns q and k by their positions, the model's
        own rule or a layer kind's."""
        return any(kind.positions == "rope" for kind, _ in self.stacks)

    @property
    def gated_experts(self) -> bool:
        """Whether an expert is three matrices, a gate beside up and down."""
        return self.expert_act in ("swiglu", "reglu")

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
    def smallthinker_21b_a3b(cls, n_layers: int = 52,
                             **changes) -> "TransformerConfig":
        """PowerInfer/SmallThinker-21BA3B-Instruct's config.json: 28 query
        heads on 4 key/value heads of 128, no q/k norm; layers 0, 4, 8, ...
        full attention with no position signal, the other three of four a
        window of 4,096 with rotary positions at 1.5e6 (`rope_layout` and
        `sliding_window_layout`, which agree); every layer 64 relu-gated
        experts of width 768, 6 a token, softmax scores renormalised over
        the chosen, routed from the layer's own input ahead of the mixer
        (the published implementation's early router); an untied head.
        `n_layers`: the model's first so many."""
        full = (("positions", "none"), ("window", 0))
        band = (("positions", "rope"), ("window", 4096))
        kinds = tuple(band if l % 4 else full for l in range(n_layers))
        return dataclasses.replace(cls(
            vocab_size=151936, d_model=2560, n_heads=28, n_layers=n_layers,
            d_ff=768, max_seq=16384, positions="none", rope_theta=1.5e6,
            norm_eps=1e-6, ffn="moe", n_experts=64, top_k=6, gates="renorm",
            expert_act="reglu", router_input="layer", tied_head=False,
            attn_core="flash", head_size=128, n_kv_heads=4,
            layer_kinds=kinds), **changes)

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

    def init_maps(k_phi, k_b, branch, n):
        """One branch's maps at the start: Phi as every matrix; the three
        gains 0.25 (the mHC paper starts them at 0.01: there the static part
        is all there is for the first steps, and a timed state in which the
        dynamic part is rounding could not tell a program that drops it);
        the biases normal(0, 1) with 2 more on the diagonal of the streams'
        own map, so that H_pre and H_post differ a stream and H_res is
        neither uniform nor the identity (no balancing step moves a bias
        here either: drawn, as `router_bias` is)."""
        b = jax.random.normal(k_b, (2 * n + n * n,), jnp.float32)
        b = b.at[2 * n:].add(2.0 * jnp.eye(n, dtype=jnp.float32).ravel())
        return {f"{branch}_phi": dense(k_phi, (n * D, 2 * n + n * n)),
                f"{branch}_a": jnp.full((3,), 0.25, jnp.float32),
                f"{branch}_b": b}

    def init_layer(key, cfg):
        # The mixer's leaves are its record's. The feed-forward's are drawn
        # from the layer's first split ([2] on), the shared expert's from [3]
        # to [5] of the split of fold 1, its gate from [5] of fold 2's and the
        # router's bias from [4] of fold 3's: the first keys of each of those
        # splits are a mixer's, and the numbers are fixed because the states
        # of the cells are.
        F, E = cfg.d_ff, cfg.n_experts
        lk = _layer_keys(key, cfg)
        mixer = mixer_of(cfg)
        # a layer of one branch has that branch's norm alone
        layer = {}
        if mixer is not None:
            layer["ln1_scale"] = unit(cfg, (D,))
        if cfg.ffn != "none":
            layer["ln2_scale"] = unit(cfg, (D,))
        if cfg.post_norms:  # one behind each branch the layer has
            for pre in list(layer):
                layer[pre.replace("_scale", "_post_scale")] = unit(cfg, (D,))
        if mixer is not None:
            layer.update(mixer.init(key, cfg, dense, unit))
        gated = cfg.ffn == "swiglu" or cfg.gated_experts
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
                    jax.random.split(jax.random.fold_in(key, 3), 5)[4],
                    (E,), jnp.float32)
            if cfg.shared_ff:
                xk = jax.random.split(jax.random.fold_in(key, 1), 6)
                if gated:
                    layer["shared_gate"] = dense(xk[3], (D, cfg.shared_ff))
                layer["shared_up"] = dense(xk[4], (D, cfg.shared_ff))
                layer["shared_down"] = dense(xk[5], (cfg.shared_ff, D))
            if cfg.shared_gate:
                layer["w_shared_gate"] = dense(
                    jax.random.split(jax.random.fold_in(key, 2), 6)[5], (D, 1))
        if cfg.streams > 1:  # a branch's maps, from fold 7: no other's keys
            hk = jax.random.split(jax.random.fold_in(key, 7), 4)
            for at, (branch, there) in enumerate((("hc1", mixer is not None),
                                                  ("hc2", cfg.ffn != "none"))):
                if there:
                    layer.update(init_maps(hk[2 * at], hk[2 * at + 1], branch,
                                           cfg.streams))
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

    A mixer's leaves as its record says (`mixers.mixer_of(cfg).pspecs`:
    column-parallel projections, a row-parallel wo). Column-parallel
    w_in/w_gate/w_up (shard output features over tp), row-parallel
    w_out/w_down (shard input features over tp), a shared expert like a
    gated-silu feed-forward (two matrices each under `expert_act` "relu2",
    three under "reglu" as under "swiglu");
    a layer of one branch has that branch's leaves alone; embedding and an
    untied head sharded over vocab; an expert stack over `ep_axis` on its
    expert dimension, the router whole; the maps of several residual streams
    (`hc1_*`, `hc2_*`) whole on every chip. Layer-stacked leaves have a leading
    layer axis (unsharded); a configuration with `layer_kinds` has a tuple of
    such stacks. A multi-token-prediction module's block is sharded like a
    layer of its kind, its norms and projection whole.
    """
    t, e = tp_axis, ep_axis

    def stack_specs(cfg):
        mixer = mixer_of(cfg)
        layers = {}
        if mixer is not None:
            layers.update(ln1_scale=P(None))
        if cfg.ffn != "none":
            layers.update(ln2_scale=P(None))
        if cfg.post_norms:
            layers.update({name.replace("_scale", "_post_scale"): P(None)
                           for name in list(layers)})
        if mixer is not None:
            layers.update(mixer.pspecs(cfg, t))
        if cfg.ffn == "gelu":
            layers.update(w_in=P(None, None, t), w_out=P(None, t, None))
        elif cfg.ffn == "swiglu":
            layers.update(w_gate=P(None, None, t), w_up=P(None, None, t),
                          w_down=P(None, t, None))
        elif cfg.ffn == "moe":
            gated = cfg.gated_experts
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
        if cfg.streams > 1:  # a branch's maps whole on every chip
            for branch, there in (("hc1", mixer is not None),
                                  ("hc2", cfg.ffn != "none")):
                if there:
                    layers.update({f"{branch}_phi": P(None, None, None),
                                   f"{branch}_a": P(None, None),
                                   f"{branch}_b": P(None, None)})
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


@_recompute
def _gelu_out(pre, w_out):
    """gelu(pre) @ w_out. Keeps the pre-activation and w_out; the
    tanh-gelu, its four temporaries and with them the matmul's operand are
    recomputed. Saving the gelu's output for that matmul instead was 0.15
    ms a step slower at bert_base's size and 0.6 GB larger (PERF.md, PR 25)."""
    return jax.nn.gelu(pre) @ w_out


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


def _expert_layer(h, layer, cfg: TransformerConfig, routing=None):
    """The expert layer on normed tokens h (T, D) -> (y (T, D), aux): the
    routed experts held here through `ops.moe.moe_ffn`, and the shared
    expert where the configuration has one, behind its sigmoid gate where it
    has that; both gated silu or two-matrix relu^2, the routed ones relu-gated
    too (`expert_act`). `routing`: the layer's `_early_routing`, made of its
    input ahead of the mixer, or None: the router reads h."""
    from kungfu_tpu.ops import moe
    from kungfu_tpu.ops.moe import moe_ffn, raw_gates, renormalised_gates, scaled

    dt = cfg.dtype
    gates = raw_gates if cfg.gates == "raw" else renormalised_gates
    gated = cfg.gated_experts
    y, aux = moe_ffn(
        h, layer["router"],
        tuple(layer[w] for w in (("w_gate", "w_up", "w_down") if gated
                                 else ("w_up", "w_down"))),
        top_k=cfg.top_k, gates=scaled(gates, cfg.routed_scale),
        expert_fn={"swiglu": moe.swiglu_experts, "relu2": moe.relu2_experts,
                   "reglu": moe.reglu_experts}[cfg.expert_act],
        held=cfg.experts_held or None, scores=cfg.router_scores,
        bias=layer["router_bias"] if cfg.router_bias else None,
        routing=routing)
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


def _taken(x, y, layer, norm: str, cfg: TransformerConfig, maps=None):
    """The residual stream x with a branch's output y in it: y behind its
    second norm where the configuration has one (`_behind`), times
    `residual_multiplier` where that is not 1. Of several streams (`maps`,
    the branch's own from `_read`), x is all of them and each takes its
    share of the others and of y: X'[i] = sum_j H_res[i, j] X[j] + H_post[i]
    y (scope `hc` > `hc_write`)."""
    y = _behind(y, layer, norm, cfg)
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    if maps is None:
        return x + y
    from kungfu_tpu.ops import hyper_connections

    # Between the barriers the mixing is ops of its own: without them XLA
    # makes the write the epilogue of the branch's last product and the next
    # maps' mean square a second result of it, under those scopes' names, and
    # no reader can say what the residual path costs (PERF.md, PR 71)
    x, y = jax.lax.optimization_barrier((x, y))
    with jax.named_scope("hc"), jax.named_scope("hc_write"):
        return jax.lax.optimization_barrier(
            hyper_connections.write(x, y, maps.res, maps.post))


def _read(x, layer, branch: str, cfg: TransformerConfig):
    """What a branch reads of the residual path, ahead of its norm -> (its
    input (B, S, D), the branch's maps or None). The one stream as it is. Of
    several (`streams` n > 1, x (B, S, n * D), `ops.hyper_connections`) the
    branch's maps from the streams and its leaves `<branch>_phi`, `_a`, `_b`
    (scope `hc` > `hc_maps`: the root mean square over all n D features, the
    product with Phi, the sigmoids and the Sinkhorn passes, float32) and u =
    sum_j H_pre[j] X[j] (`hc_read`); `_taken` writes with the same maps."""
    if cfg.streams == 1:
        return x, None
    from kungfu_tpu.ops import hyper_connections

    with jax.named_scope("hc"):
        with jax.named_scope("hc_maps"):
            maps = hyper_connections.maps(
                x, layer[f"{branch}_phi"], layer[f"{branch}_a"],
                layer[f"{branch}_b"], cfg.streams, cfg.hc_sinkhorn_iters,
                cfg.hc_eps, cfg.hc_clamp)
        with jax.named_scope("hc_read"):  # an op of its own: `_taken`'s note
            return jax.lax.optimization_barrier(
                hyper_connections.read(x, maps.pre)), maps


def _early_routing(x, layer, cfg: TransformerConfig):
    """The expert layer's routing from the layer's own input x (B, S, D), as
    it is, ahead of the first norm and the mixer (`router_input` "layer") ->
    `ops.moe.Routing`: the router's product in float32, its scores and the
    top_k under `moe_early_router`, and on one shard the order of the
    token-choices under `moe_plan`, both inside `moe`. Nothing here reads what
    the mixer computes, so the sort can run beside it. The order goes on under
    the name `moe_plan` (T x top_k int32: 0.4 MB a layer of 16,384 tokens
    and 6 a token): a layer that is run again keeps it (`_layer_again`) and
    makes the router's scores again, which the gates' derivative reads, but
    not the sort."""
    from jax.ad_checkpoint import checkpoint_name

    from kungfu_tpu.ops.moe import Routing, dispatch_plan, route

    B, S, D = x.shape
    with jax.named_scope("moe"):
        with jax.named_scope("moe_early_router"):
            made = route(x.reshape(B * S, D), layer["router"], cfg.top_k,
                         cfg.router_scores,
                         layer["router_bias"] if cfg.router_bias else None)
        with jax.named_scope("moe_plan"):
            plan = dispatch_plan(made[3], cfg.n_experts,
                                 cfg.experts_held or None)
            return Routing(made, plan._replace(
                order=checkpoint_name(plan.order, "moe_plan")))


def _mixed(x, layer, cfg: TransformerConfig, core=None, segments=()):
    """The residual path x (one stream, or all of several) with the layer's
    first branch in it -> (x, the layer's indexer loss or None): the mixer,
    its record's (`mixers.mixer_of`), under the record's scope, on what
    `_read` hands it; x as it is of a layer that is its feed-forward alone."""
    segments, marks = segments[:1], segments[1:]
    mixer = mixer_of(cfg)
    if mixer is None:
        return x, None
    u, maps = _read(x, layer, "hc1", cfg)
    with jax.named_scope(mixer.scope):
        y, index_kl = mixer.apply(u, layer, cfg, core, segments, marks)
        if maps is None:
            return _taken(x, y, layer, "ln1_post_scale", cfg), index_kl
    # the streams' own mixing outside the branch's scope: `attn` stays the mixer's
    return _taken(x, y, layer, "ln1_post_scale", cfg, maps), index_kl


def _layer(x, layer, cfg: TransformerConfig, core=None, segments=()):
    """One layer -> (x, aux): a mixer and a feed-forward, each a residual
    branch behind its own norm, or one of the two alone; with `post_norms`
    the branch's output goes through a second norm before the residual takes
    it, and `residual_multiplier` scales what it takes. x is the one
    residual stream (B, S, D), or with `streams` n > 1 all n side by side
    (B, S, n * D): each branch then reads u = sum_j H_pre[j] X[j] and the
    streams take X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y, the three maps
    the branch's own (`_read`, `_taken`). The mixer is its
    record's (`_mixed`). An expert layer whose router reads the layer's input
    (`router_input` "layer") is routed first, ahead of the mixer
    (`_early_routing`). aux is the expert
    layer's `ops.moe.MoeAux` (router losses and token-choices per expert),
    None of any other. `core`: an attention core plugged from outside.
    `segments`: (the documents' numbers (B, S),) of packed rows, for the
    mixer, with what the records wanted made of them behind them
    (`_hidden`); () where a row is one document."""
    routing = None
    if cfg.ffn == "moe" and cfg.router_input == "layer":
        routing = _early_routing(x, layer, cfg)
    x, index_kl = _mixed(x, layer, cfg, core, segments)
    x, aux = _feed_forward(x, layer, cfg, routing)
    # an indexer's record beside the expert layer's
    return x, aux if index_kl is None else LayerAux(aux, index_kl)


def _feed_forward(x, layer, cfg: TransformerConfig, routing=None):
    """A layer's second branch on the residual path x (one stream, or all of
    several: `_read`) -> (x, the expert layer's aux or None); `routing`, an
    expert layer's made ahead of the mixer (`_early_routing`), or None."""
    dt, eps = cfg.dtype, cfg.norm_eps
    if cfg.ffn == "none":
        return x, None
    u, maps = _read(x, layer, "hc2", cfg)
    aux = None
    with jax.named_scope("moe" if cfg.ffn == "moe" else "ffn"):
        h = _rmsnorm(u, _scale(layer["ln2_scale"], cfg), eps)
        if cfg.ffn == "moe":
            B, S, D = u.shape
            y, aux = _expert_layer(h.reshape(B * S, D), layer, cfg, routing)
            y = y.reshape(B, S, D)
        elif cfg.ffn == "swiglu":
            y = _silu_gate_out(h @ layer["w_gate"].astype(dt),
                               h @ layer["w_up"].astype(dt),
                               layer["w_down"].astype(dt))
        else:
            y = _gelu_out(h @ layer["w_in"].astype(dt),
                          layer["w_out"].astype(dt))
        if maps is None:
            return _taken(x, y, layer, "ln2_post_scale", cfg), aux
    return _taken(x, y, layer, "ln2_post_scale", cfg, maps), aux


# `layer_remat`: the scan keeps the layer's input and, of what the layer
# computes, the flash core's output and row sums, whatever the call (0.15 GB
# a layer of 72 heads of 128 at 8,192 positions, 0.085 GB a layer of 20
# heads of 256), and a Gated DeltaNet or KDA mixer's output (`gdn_mix`: 0.067
# GB a layer of 16,384 positions of 2,048; `kda_mix`): the projections, the rotation and the
# feed-forward are run again in the backward pass, the forward kernel and
# the DeltaNet mixer's head blocks are not (the blocks run their forward
# once more for their own gradients, `_delta_heads`: twice a step in all).
# Of a short-convolution mixer nothing is kept: its op's forward kernel is one
# pass over the projection's output (0.2 ms a layer of 8,192 positions of
# 2,048 channels) and runs again with the projection that feeds it. Of a
# learned sparse index the choice is kept as a bit a pair (`dsa_chosen`, 8.4 MB
# a layer of 8,192 positions) beside its core's output and row sums: the
# indexer's scores are made again (268 MB a layer, read by the indexer's
# loss), the search for each query's keys is not. Of an expert layer routed
# ahead of its mixer (`router_input` "layer") the order of the token-choices
# is kept (`moe_plan`, 0.4 MB a layer of 98,304 choices): the router's
# product, scores and top-k are made again, for the gates' derivative, the
# stable sort is not.
# Under a loop every application of a layer keeps its own: the Ouro cell's 32
# applications (8 layers x 4 loop steps) of 16 heads of 128 at 4,096 positions
# keep 2 x 16.8 MB each and the row sums, 1.08 GB a step beside the 0.82 GB of
# bfloat16 copies of the 8 layers' weights: kept activations to weights four
# times any other cell's
_layer_again = jax.checkpoint(
    _layer, static_argnums=(2,), prevent_cse=False,
    policy=jax.checkpoint_policies.save_only_these_names(
        "flash_out", "flash_lse", "gdn_mix", "kda_mix", "dsa_chosen",
        "moe_plan"))


def _block(x, layer, cfg: TransformerConfig, core=None):
    """One layer's hidden states alone, for the paths that have no place
    for an expert layer's auxiliary losses (pipeline, ring, a plugged
    core). A mixer that is built for the normal path alone says so in its
    record, with the reason."""
    mixer = mixer_of(cfg)
    if mixer is not None and mixer.off_the_normal_path:
        raise NotImplementedError(mixer.off_the_normal_path)
    if cfg.streams > 1:
        raise NotImplementedError(
            "residual streams (streams > 1) are built for the normal path "
            "alone: the ring and pipeline paths hand a layer one stream "
            "(B, S, D) a shard or a stage, and nothing there enters or "
            "leaves the streams")
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
    if S > cfg.max_seq and cfg.rotary:
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


def _enter(x, cfg: TransformerConfig):
    """The residual path's start from the embedding's (or a module's
    projection's) output x (B, S, D): x itself, or of `streams` n > 1 the n
    streams (B, S, n * D), each a copy of it (scope `hc_in`)."""
    if cfg.streams == 1:
        return x
    from kungfu_tpu.ops import hyper_connections

    with jax.named_scope("hc_in"):
        return hyper_connections.enter(x, cfg.streams)


def _leave(x, cfg: TransformerConfig):
    """The residual path's end, ahead of a final norm: x itself, or the sum
    of the streams (B, S, n * D) -> (B, S, D) (scope `hc_out`)."""
    if cfg.streams == 1:
        return x
    from kungfu_tpu.ops import hyper_connections

    with jax.named_scope("hc_out"):
        return hyper_connections.leave(x, cfg.streams)


def _loop_step_end(u, params, cfg: TransformerConfig, each):
    """The end of a loop step on the stacks' output u: the model's final
    norm (scope `loop_norm`) -> (what the next loop step reads, what the
    loop hands back of this one), both of the normed state."""
    with jax.named_scope("loop_norm"):
        x = _rmsnorm(u, _scale(params["ln_f_scale"], cfg), cfg.norm_eps)
    return x, each(x) if each else x


def _hidden(params, tokens, cfg: TransformerConfig, each=None):
    """-> (final hidden states (B, S, D), the expert layers' stacked aux or
    None), one scan for each stack of layers of one kind. With `streams` n >
    1 the embedding's output enters n streams, the scans carry (B, S, n * D)
    and what is handed back is the streams' sum (`_enter`, `_leave`). Under plain S-SGD on
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
    x = _enter(_embed(params, tokens, cfg), cfg)
    # the documents of packed rows: constants of every layer scan
    packed = _segments(tokens, cfg)
    if packed:
        records = (mixer_of(kind) for kind, _ in cfg.stacks)
        for make in dict.fromkeys(m.document_marks for m in records
                                  if m is not None and m.document_marks):
            with jax.named_scope("segments"):  # once a step, for every such layer
                packed += (make(*packed[:1]),)
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
    x = _leave(x, cfg)
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
    block's own. Under several residual streams x is the stack's collapsed
    output, h' enters streams of the module's own as the embedding does, the
    block has maps of its own and its output leaves by the sum."""
    mtp, kind = params["mtp"], cfg.mtp_kind
    z, aux = (_layer_again if kind.layer_remat else _layer)(
        _enter(_mtp_input(params, x, tokens_next, cfg), kind), mtp["layer"], kind)
    return _leave(z, kind), aux


def _mtp_input(params, x, tokens_next, cfg: TransformerConfig):
    """What the module's block reads, h' of `_mtp_hidden` (B, S, D); scope
    `mtp_proj`."""
    mtp = params["mtp"]
    with jax.named_scope("mtp_proj"):
        e = _rmsnorm(_embed(params, tokens_next, cfg),
                     _scale(mtp["enorm_scale"], cfg), cfg.norm_eps)
        h = _rmsnorm(x, _scale(mtp["hnorm_scale"], cfg), cfg.norm_eps)
        return jnp.concatenate([e, h], axis=-1) @ mtp["eh_proj"].astype(cfg.dtype)


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
    changed against a choice on the scores alone; of a share of the experts
    (`experts_held`) `chunk_rows` (L,), the rows of the chunks that the
    share's loop ran (`ops.moe._live_chunks` times `_share_chunk`, the two
    that the layer itself asks), of which `held_rows` lie in a group, and
    `rows_moved` (L,), those of them that the dispatch and the combine move
    (`ops.row_moves.rows_visited` a chunk where the rows' kernels run,
    `tiling` asked as the layer asks: the live rows rounded up to a row
    tile; every row under a selection bias and where XLA's forms stand); where
    the experts' rows take the grouped matmul's kernels
    (`ops.grouped_matmul.tiling`, asked as the layer asks) `tile_visits`
    (L,), the visits the kernels make of row tiles for the groups that came
    (`ops.grouped_matmul.tile_visits`, a share's chunk by chunk), and `tiles`
    (L,), the row tiles of the buffers they were handed. With a
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
    from kungfu_tpu.ops import moe as moe_ops, row_moves
    from kungfu_tpu.ops.grouped_matmul import tile_visits, tiling

    chunk = choices  # rows a call of the grouped matmul: all, or a share's chunk
    if held < moe.n_experts:
        chunk = moe_ops._share_chunk(tokens.size, moe.top_k, held,
                                     moe.n_experts, moe.router_bias)
        chunks = jnp.asarray(
            [moe_ops._live_chunks(moe.top_k, chunk, tokens.size, sizes)
             for sizes in counts], jnp.int32)
        stats["chunk_rows"] = chunk * chunks
        # of those rows the ones the dispatch and the combine move: the rows
        # of the tiles their kernels visit, every row where XLA's forms stand
        moves = None if moe.router_bias else row_moves.tiling(
            chunk, moe.d_model, tokens.size)
        stats["rows_moved"] = stats["chunk_rows"] if moves is None else sum(
            jnp.where(i < chunks, row_moves.rows_visited(jnp.clip(
                stats["held_rows"] - i * chunk, 0, chunk), moves.tm), 0)
            for i in range(-(-tokens.size * min(moe.top_k, held) // chunk)))
    fill = moe_ops.GROUPED_WIDTH if moe.expert_act == "relu2" else 1
    tiles = tiling(chunk, -(-moe.d_model // fill) * fill,
                   -(-moe.d_ff // fill) * fill, held)
    if tiles is not None and held == moe.n_experts:
        stats["tile_visits"] = jnp.sum(tile_visits(counts, tiles.tm), axis=-1)
        stats["tiles"] = jnp.full(counts.shape[:1], choices // tiles.tm)
    elif tiles is not None:
        def visits(sizes, i):  # of chunk i, the groups `_chunk_part` hands on
            return jnp.sum(tile_visits(moe_ops._chunk_groups(
                tokens.size, moe.top_k, chunk, sizes, i, moe.router_bias),
                tiles.tm))

        most = tokens.size * min(moe.top_k, held)
        stats["tile_visits"] = sum(
            jnp.where(i < chunks, jnp.stack([visits(sizes, i) for sizes in counts]), 0)
            for i in range(-(-most // chunk)))
        stats["tiles"] = chunks * (chunk // tiles.tm)
    return stats


def _each_layer(params, cfg: TransformerConfig):
    """(the layer's kind, its leaves with no layer axis) of every layer, in
    the model's order: for what goes through the layers one after another
    beside the step, and not in a scan."""
    stacks = params["layers"] if cfg.layer_kinds else (params["layers"],)
    for (kind, n), stacked in zip(cfg.stacks, stacks, strict=True):
        for at in range(n):
            yield kind, jax.tree.map(lambda leaf: leaf[at], stacked)


def gate_zero_shares(params, tokens, cfg: TransformerConfig):
    """What relu-gated experts (`expert_act` "reglu") leave for a kernel to
    skip, expert layer by expert layer, for tokens (B, S): (L,) float32, the
    share of the elements of relu(W_gate,e m) that are exactly 0 over the
    rows computed here, token-choice (t, e) with e held, m the normed state
    the experts read. Each such element multiplies a row of W_down,e that
    need not be read. Jit this beside the step, as `routing_stats`; the
    layers one after another, a held expert at a time over all the tokens in
    the compute type, as the step makes the product."""
    from kungfu_tpu.ops.moe import route

    if not any(kind.ffn == "moe" and kind.expert_act == "reglu"
               for kind, _ in cfg.stacks):
        raise ValueError("gate_zero_shares: the configuration has no expert "
                         "layer of relu-gated experts (expert_act 'reglu')")
    if cfg.streams > 1:
        raise ValueError("gate_zero_shares walks one residual stream through "
                         "the layers: no model the repo runs has relu-gated "
                         "experts under several (streams > 1)")
    shares = []
    x = _embed(params, tokens, cfg)
    for kind, layer in _each_layer(params, cfg):
        routed = kind.ffn == "moe"
        routing = (_early_routing(x, layer, kind)
                   if routed and kind.router_input == "layer" else None)
        mid = _mixed(x, layer, kind)[0]
        if routed and kind.expert_act == "reglu":
            m = _rmsnorm(mid, _scale(layer["ln2_scale"], kind),
                         kind.norm_eps).reshape(-1, kind.d_model)
            chosen = (routing.route if routing else route(
                m, layer["router"], kind.top_k, kind.router_scores,
                layer["router_bias"] if kind.router_bias else None))[3]
            first, held = kind.experts_held or (0, kind.n_experts)

            def zeros_of(e):  # called at once, in this turn of the loop
                mine = jnp.any(chosen == first + e, axis=-1)  # (T,)
                pre = m @ layer["w_gate"][e].astype(kind.dtype)
                return (jnp.sum((pre <= 0) & mine[:, None], dtype=jnp.float32),
                        jnp.sum(mine, dtype=jnp.float32) * pre.shape[-1])

            zero, of = jax.lax.map(zeros_of, jnp.arange(held))
            shares.append(jnp.sum(zero) / jnp.maximum(jnp.sum(of), 1.0))
        x = _feed_forward(mid, layer, kind, routing)[0]
    return jnp.stack(shares)


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
        chosen.append(_sparse_choice(_mixer_input(x, layer, cfg), layer, cfg)[1])
        x = _layer(x, layer, cfg)[0]
    return jnp.stack(chosen)


def record_routing(stats, registry=None) -> None:
    """`routing_stats`' numbers as gauges of `telemetry.metrics`, a series
    a layer: `kungfu_moe_dropped_token_choices`, `kungfu_moe_max_over_mean_load`,
    `kungfu_moe_held_rows` and `kungfu_moe_held_share` (the token-choices
    computed here, and their share of all the layer's), of a share of the
    experts `kungfu_moe_chunk_fill_share` (the held rows over the rows of
    the chunks that ran, 1 where none did: the rest are rows of no group
    that were gathered, weighed and scattered all the same) and
    `kungfu_moe_rows_moved_share` (the rows that the dispatch and the combine
    move over the same rows: the fill rounded up to a row tile where
    `ops/row_moves.py`'s kernels run, 1 under a selection bias, wherever XLA's
    gather and scatter-add stand and where no chunk ran), where the
    grouped matmul's kernels run `kungfu_moe_tile_visit_share` (the row tiles
    they visit over the tiles of the buffers they get: 1 where every tile is
    one group's, more by what the groups' edges cost, a share's fill where
    its chunk is part full, 0 where no chunk ran), per expert held
    `kungfu_moe_expert_token_choices`, under a selection bias
    `kungfu_moe_bias_moved_token_choices`, and where `stats` holds
    `gate_zero_share` (`gate_zero_shares`' row, put there by the caller)
    `kungfu_moe_gate_zero_share`."""
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
    fill = reg.gauge("kungfu_moe_chunk_fill_share",
                     "held rows over the rows of the share's chunks that ran",
                     ("layer",)) if "chunk_rows" in stats else None
    moved_rows = reg.gauge("kungfu_moe_rows_moved_share",
                           "rows the share's dispatch and combine move over "
                           "the rows of its chunks that ran",
                           ("layer",)) if "rows_moved" in stats else None
    visit = reg.gauge("kungfu_moe_tile_visit_share",
                      "row tiles the grouped matmul's kernels visit over the "
                      "tiles of their buffers",
                      ("layer",)) if "tile_visits" in stats else None
    moved = reg.gauge("kungfu_moe_bias_moved_token_choices",
                      "token-choices the router's selection bias changed",
                      ("layer",)) if "bias_moved" in stats else None
    zero = reg.gauge("kungfu_moe_gate_zero_share",
                     "the share of the held rows' relu(w_gate x) that is "
                     "exactly 0", ("layer",)) if "gate_zero_share" in stats else None
    choices = stats["chosen"][0].size
    for i, row in enumerate(np.asarray(stats["counts"])):
        layer = int(stats["layer"][i])
        dropped.labels(layer).set(float(stats["dropped"][i]))
        skew.labels(layer).set(float(stats["max_over_mean"][i]))
        rows.labels(layer).set(float(stats["held_rows"][i]))
        share.labels(layer).set(float(stats["held_rows"][i]) / choices)
        if fill is not None:
            ran = float(stats["chunk_rows"][i])
            fill.labels(layer).set(float(stats["held_rows"][i]) / ran
                                   if ran else 1.0)
        if moved_rows is not None:
            ran = float(stats["chunk_rows"][i])
            moved_rows.labels(layer).set(float(stats["rows_moved"][i]) / ran
                                         if ran else 1.0)
        if visit is not None:
            tiles = float(stats["tiles"][i])
            visit.labels(layer).set(float(stats["tile_visits"][i]) / tiles
                                    if tiles else 0.0)
        if moved is not None:
            moved.labels(layer).set(float(stats["bias_moved"][i]))
        if zero is not None:
            zero.labels(layer).set(float(stats["gate_zero_share"][i]))
        for expert, n in enumerate(row):
            load.labels(layer, expert).set(float(n))


def residual_stats(params, tokens, cfg: TransformerConfig):
    """What the maps of a model of several residual streams (`streams` n >
    1) do with tokens (B, S), a row a layer and branch in the model's order:
    jit this beside the step, as `routing_stats`. `layer` and `branch` (1
    the mixer's, 2 the feed-forward's) say which; `res_diagonal`, the mean
    over positions and streams of H_res[i, i], how much a stream keeps of
    itself; `res_sum_error`, the largest |row or column sum - 1| of any
    position's H_res, what the Sinkhorn passes leave; `pre_mean` and
    `post_mean`, the mean H_pre and H_post. The layers one after another
    and not in a scan. With a multi-token-prediction module, tokens (B, S +
    1), and the module's block is the last rows, `layer` = n_layers."""
    if cfg.streams == 1:
        raise ValueError("residual_stats: the configuration has one residual "
                         "stream (streams 1) and no map to read")
    if cfg.mtp_depth:
        tokens, tokens_next = tokens[:, :-1], tokens[:, 1:]
    rows = []

    def through(x, layer, kind, at):
        for branch, run in ((1, _mixed), (2, _feed_forward)):
            if f"hc{branch}_phi" in layer:
                maps = _read(x, layer, f"hc{branch}", kind)[1]
                n = kind.streams
                sums = jnp.stack([jnp.sum(maps.res, axis=0),
                                  jnp.sum(maps.res, axis=1)])
                rows.append((at, branch,
                             jnp.mean(jnp.stack([maps.res[i, i] for i in range(n)])),
                             jnp.max(jnp.abs(sums - 1.0)),
                             jnp.mean(maps.pre), jnp.mean(maps.post)))
            x = run(x, layer, kind)[0]
        return x

    x = _enter(_embed(params, tokens, cfg), cfg)
    for at, (kind, layer) in enumerate(_each_layer(params, cfg)):
        x = through(x, layer, kind, at)
    if cfg.mtp_depth:
        kind = cfg.mtp_kind
        through(_enter(_mtp_input(params, _leave(x, cfg), tokens_next, cfg), kind),
                params["mtp"]["layer"], kind, cfg.n_layers)
    names = ("layer", "branch", "res_diagonal", "res_sum_error", "pre_mean",
             "post_mean")
    return {name: jnp.stack([jnp.asarray(row[i]) for row in rows])
            for i, name in enumerate(names)}


def record_residual(stats, registry=None) -> None:
    """`residual_stats`' numbers as gauges of `telemetry.metrics`, a series
    a layer and branch (`branch` "mixer" or "ffn"):
    `kungfu_hc_res_diagonal`, `kungfu_hc_res_sum_error`,
    `kungfu_hc_pre_mean` and `kungfu_hc_post_mean`."""
    from kungfu_tpu.telemetry import metrics

    reg = registry or metrics.REGISTRY
    gauges = {
        name: reg.gauge(f"kungfu_hc_{name}", text + ", of the batch read last",
                        ("layer", "branch"))
        for name, text in (
            ("res_diagonal", "the mean of H_res's diagonal: what a residual "
             "stream keeps of itself around the branch"),
            ("res_sum_error", "the largest |row or column sum - 1| of any "
             "position's H_res: what the Sinkhorn passes leave"),
            ("pre_mean", "the mean of H_pre, a stream's weight in the "
             "branch's input"),
            ("post_mean", "the mean of H_post, the weight of the branch's "
             "output in a stream"))}
    for i, layer in enumerate(np.asarray(stats["layer"])):
        branch = "mixer" if int(stats["branch"][i]) == 1 else "ffn"
        for name, gauge in gauges.items():
            gauge.labels(int(layer), branch).set(float(stats[name][i]))


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
