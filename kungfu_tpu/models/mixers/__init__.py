"""A layer's token mixer is one record of this table, and each mixer one
module of this package with everything that is its own: its refusals, its
leaves and the keys they are drawn from, their shardings, and the function
from the layer's input to the branch's output. `models/transformer.py` asks
the record (`mixer_of`) wherever it used to know the mixers by name: the
configuration's `__post_init__`, `init_transformer`, `param_pspecs`, `_layer`,
`_block` and `_hidden`. The next mixer is a module here, an entry in `MIXERS`
and its fields in `TransformerConfig`. Six mixers stand in the table:
`attention`, `gated_delta`, `kda` (PR 69), `latent`, `mamba2`, `short_conv`.
`latent` takes `latent_dims[0]` 0 for no q latent, `positions` "none" for
nothing turned, and value heads whose size `hd_v` differs from the q/k heads'
on the flash core as on the dense one; under `yarn` its rotated features
turn at YaRN's frequencies (PR 71), and a scale of the scores' own goes
through `attention_multiplier` to the flash core. A mixer reads (B, S, D)
whatever the residual path is: of several streams (`streams` > 1) the layer
hands it the branch's input read out of them (`transformer._read`) and takes
its output back into them.

The arrows: `ops/` <- `models/blocks.py` <- this package <-
`models/transformer.py`. Nothing here imports `models/transformer.py` (the
configuration reaches a mixer as an argument), and a mixer imports its op
inside the function that calls it, so that importing the model imports no
Pallas kernel.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

from kungfu_tpu.models.mixers import (attention, gated_delta, kda, latent,
                                      mamba2, short_conv)


@dataclasses.dataclass(frozen=True)
class Mixer:
    """What the layer asks of one token mixer.

    `scope`: the name of the branch's scope, which the per-layer metrics read.
    `init(key, cfg, dense, unit)` -> the mixer's leaves of one layer, `key`
    the layer's, `dense(key, shape)` a matrix at the model's scale and
    `unit(cfg, shape)` a norm's weight at its start; `pspecs(cfg, tp)` -> a
    PartitionSpec for each of those leaves, the stack's layer axis in front:
    the same names, whatever the configuration. `apply(x, layer, cfg, core,
    segments, marks)` -> (the branch's output, the layer's indexer loss or
    None) from the layer's input x (B, S, D), which the mixer puts behind the
    layer's first norm itself (`blocks._mixer_input`); `core` is an attention
    core plugged from outside or None, `segments` (the documents' numbers,)
    of packed rows or (), `marks` what the stacks' `document_marks` made of
    them. `check(cfg)` raises the mixer's own refusals, a ValueError with a
    sentence each. `off_the_normal_path`: the sentence the ring and pipeline
    paths (`transformer._block`) refuse the mixer with, "" where they build
    it. `keeps_documents_apart(cfg)`: whether no tap, state or key of the
    mixer reaches into another document of a packed row (`end_of_document`).
    `document_marks(segments)`: what the mixer wants made once a step from
    the documents' numbers, or None."""
    scope: str
    init: Callable
    pspecs: Callable
    apply: Callable
    check: Callable = lambda cfg: None
    off_the_normal_path: str = ""
    keeps_documents_apart: Callable = lambda cfg: False
    document_marks: Optional[Callable] = None


# the keys are what `TransformerConfig` accepts for `mixer`, beside "none"
MIXERS = {
    "attention": Mixer(
        "attn", attention.init, attention.pspecs, attention.apply,
        keeps_documents_apart=attention.on_the_flash_core),
    "gated_delta": Mixer(
        "gdn", gated_delta.init, gated_delta.pspecs, gated_delta.apply,
        check=gated_delta.check),
    "kda": Mixer(
        "kda", kda.init, kda.pspecs, kda.apply, check=kda.check,
        off_the_normal_path=kda.OFF_THE_NORMAL_PATH),
    "latent": Mixer(
        "attn", latent.init, latent.pspecs, latent.apply, check=latent.check),
    "mamba2": Mixer(
        "ssm", mamba2.init, mamba2.pspecs, mamba2.apply, check=mamba2.check,
        keeps_documents_apart=lambda cfg: True,
        document_marks=mamba2.document_marks),
    "short_conv": Mixer(
        "sconv", short_conv.init, short_conv.pspecs, short_conv.apply,
        check=short_conv.check,
        off_the_normal_path=short_conv.OFF_THE_NORMAL_PATH,
        keeps_documents_apart=lambda cfg: True),
}

# softmax attention under a learned sparse index (`sparse_index`), whose
# `check` refuses every mixer but "attention"
SPARSE_ATTENTION = Mixer(
    "attn", attention.sparse_init, attention.sparse_pspecs,
    attention.sparse_apply, check=attention.sparse_check,
    off_the_normal_path=attention.SPARSE_OFF_THE_NORMAL_PATH)


def mixer_of(cfg) -> Optional[Mixer]:
    """The record of the configuration's token mixer; None of a layer that
    is its feed-forward alone (`mixer` "none")."""
    if cfg.sparse_index:
        return SPARSE_ATTENTION
    return None if cfg.mixer == "none" else MIXERS[cfg.mixer]
