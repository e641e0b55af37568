"""granite-4.0-h-micro's layers in `models/transformer.py` on packed documents
(PR 52): every layer a Mamba-2 or attention mixer and a gated feed-forward,
four multipliers, a tied head behind a state-space stack, and rows that are
several documents, kept apart in the convolution, the scan and the flash
kernels; against the plain float32 reference
`benchmark/reference/granite_hybrid.py` at a small size on the CPU; each
mechanism knocked out in turn in `tests/test_granite_hybrid_faults.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import family_cases as fc
from benchmark import harness
from benchmark.reference import granite_hybrid as ref
from family_cases import *  # noqa: F401,F403  the shared cases
from kungfu_tpu.models import transformer
from kungfu_tpu.ops import ssm_scan
from kungfu_tpu.telemetry import metrics


def _named_specs(specs):
    mamba, attention, _ = specs["layers"]
    assert mamba["w_ssm_in"] == mamba["conv_w"] == PartitionSpec(None, None, "tp")
    assert mamba["wo"] == attention["wo"] == PartitionSpec(None, "tp", None)
    for layer in (mamba, attention):  # both branches in every layer
        assert layer["w_gate"] == layer["w_up"] == PartitionSpec(None, None, "tp")
        assert layer["w_down"] == PartitionSpec(None, "tp", None)
        assert layer["ln1_scale"] == layer["ln2_scale"] == PartitionSpec(None)
    assert attention["wq"] == attention["wk"] == PartitionSpec(None, None, "tp")
    assert "lm_head" not in specs and specs["embed"] == PartitionSpec("tp", None)


FAMILY = fc.GRANITE_HYBRID.with_cases(
    named_specs=_named_specs, tp_leaf=("layers", 0, "w_ssm_in"))
family, tiny_config, CONFIG = FAMILY.module, FAMILY.tiny_config, FAMILY.config


@pytest.fixture(autouse=True)
def four_chunks_a_row(monkeypatch):
    """The scan in chunks of 32: a row of the tests' 128 positions is four,
    and the sample's boundaries stand inside them and on their edges."""
    monkeypatch.setattr(ssm_scan, "CHUNK", fc.GRANITE_CHUNK)


def test_every_layer_is_a_mixer_and_a_gated_feed_forward():
    assert family.layer_types(CONFIG) == ["mamba", "attention", "mamba"]
    mc = family.model_config(CONFIG)
    assert [(kind.mixer, kind.ffn, kind.layer_remat, n) for kind, n in mc.stacks] == [
        ("mamba2", "swiglu", True, 1), ("attention", "swiglu", True, 1),
        ("mamba2", "swiglu", True, 1)]
    assert (mc.embedding_multiplier, mc.attention_multiplier,
            mc.residual_multiplier, mc.logits_scaling) == (12.0, 0.015625, 0.22, 8.0)
    assert (mc.positions, mc.tied_head, mc.end_of_document) == ("none", True, 0)
    assert mc.ssm_dims == (8, 16, 16, 1) and (mc.head_dim, mc.kv_heads) == (16, 2)
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    assert set(state) == {"embed", "ln_f_scale", "layers"}  # tied, no positions
    shapes = [{k: v.shape for k, v in stack.items()} for stack in state["layers"]]
    ffn = {"ln2_scale": (1, 64), "w_gate": (1, 64, 96), "w_up": (1, 64, 96),
           "w_down": (1, 96, 64)}
    mamba = {"ln1_scale": (1, 64), "w_ssm_in": (1, 64, 128 + 128 + 2 * 16 + 8),
             "conv_w": (1, 4, 128 + 2 * 16), "conv_b": (1, 128 + 2 * 16),
             "dt_bias": (1, 8), "A_log": (1, 8), "D_skip": (1, 8),
             "ssm_norm_scale": (1, 128), "wo": (1, 128, 64), **ffn}
    attention = {"ln1_scale": (1, 64), "wq": (1, 64, 64), "wk": (1, 64, 32),
                 "wv": (1, 64, 32), "wo": (1, 64, 64), **ffn}
    assert shapes == [mamba, attention, mamba]


def test_the_sample_has_boundaries_inside_a_chunk_and_on_its_edge():
    tokens = FAMILY.sample()[:, :-1]
    rows = [family.row_documents(CONFIG, row) for row in tokens]
    assert rows == [[10, 22, 1, 38, 57], [100, 28]]
    starts = np.cumsum(rows[0])[:-1]
    assert any(s % fc.GRANITE_CHUNK == 0 for s in starts)  # on an edge
    assert sum(s % fc.GRANITE_CHUNK != 0 for s in starts) >= 2  # inside
    assert max(rows[0]) > fc.GRANITE_CHUNK  # and a chunk with none
    # what the family draws by itself is packed too, every row
    drawn = family.host_batch(CONFIG, 11, 0, 8)
    assert drawn.shape == (8, 129) and drawn.dtype == np.int32
    assert all(len(family.row_documents(CONFIG, row)) >= 2 for row in drawn)
    assert 0 <= drawn.min() and drawn.max() < CONFIG["vocab_size"]
    np.testing.assert_array_equal(drawn, family.host_batch(CONFIG, 11, 0, 8))
    assert not np.array_equal(drawn, family.host_batch(CONFIG, 11, 1, 8))
    assert not np.array_equal(drawn, family.host_batch(CONFIG, 2 ** 31 + 5, 0, 8))


def test_a_packed_row_is_its_documents_run_one_at_a_time():
    """The program's hidden states of row 0's documents, each run as a row
    of its own (padded behind its end-of-document id with a further
    document, which it cannot see), are the packed row's; with no
    end-of-document id named the same row reads otherwise."""
    mc = family.model_config(CONFIG)
    state, tokens = FAMILY.state(), jnp.asarray(FAMILY.sample()[:1, :-1])
    hidden = jax.jit(lambda p, t: transformer.transformer_hidden(p, t, mc))
    packed = hidden(state, tokens)
    at = 0
    for length in family.row_documents(CONFIG, tokens[0]):
        alone = jnp.concatenate(
            [tokens[:, at:at + length],
             jnp.full((1, 128 - length), 7, tokens.dtype)], axis=1)
        got = hidden(state, alone)[:, :length]
        assert harness.relative_error(got, packed[:, at:at + length]) <= 2e-6
        at += length
    one_document = dataclasses.replace(mc, end_of_document=None)
    unpacked = jax.jit(lambda p, t: transformer.transformer_hidden(
        p, t, one_document))(state, tokens)
    assert harness.relative_error(unpacked[:, :10], packed[:, :10]) <= 2e-6
    assert harness.relative_error(unpacked[:, 10:], packed[:, 10:]) > 1e-2


def test_the_logits_are_the_references_on_the_tied_embedding():
    mc = family.model_config(CONFIG)
    state, sample = FAMILY.state(), FAMILY.sample()
    got = jax.jit(lambda p, t: transformer.transformer_apply(p, t, mc))(
        state, sample[:, :-1])
    want = ref.logits(state, sample, **family._hyper(CONFIG))
    assert got.shape == want.shape == (2, 128, 320)
    assert harness.relative_error(got, want) <= 1e-5


def test_packing_stats_are_the_benchmarks_own_count():
    sample = FAMILY.sample()
    stats = family.packing_stats(CONFIG, sample)
    rows = [family.row_documents(CONFIG, row[:-1]) for row in sample]
    assert stats["documents"] == [len(row) for row in rows] == [5, 2]
    assert stats["shortest"] == [1, 28] and stats["longest"] == [57, 100]
    pairs = [sum(l * (l + 1) // 2 for l in row) for row in rows]
    np.testing.assert_allclose(stats["within_document_pairs"],
                               np.asarray(pairs) / (128 * 129 // 2), rtol=1e-6)
    assert family.within_document_pairs(CONFIG, sample) == np.mean(pairs)
    registry = metrics.Registry()
    transformer.record_packing(stats, registry)
    text = registry.render()
    assert "kungfu_packed_documents_per_row 3.5" in text
    assert "kungfu_packed_shortest_document 1" in text
    assert "kungfu_packed_longest_document 100" in text
    assert "kungfu_packed_within_document_pairs 0." in text
    with pytest.raises(ValueError, match="one document"):
        transformer.packing_stats(sample, dataclasses.replace(
            family.model_config(CONFIG), end_of_document=None))


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_the_dead_block_share_is_the_blocks_the_kernels_skip(seed):
    """The family's count against the kernels' own rule on the numbers the
    model hands them (`_segments`): of the blocks under the diagonal, those
    that `block_counts` leaves out for a row's documents (PR 53: they run no
    body)."""
    from kungfu_tpu.models.transformer import _segments
    from kungfu_tpu.ops.flash_attention import block_counts

    batch = family.host_batch(CONFIG, seed, 0, 3)
    blk_q, blk_k = CONFIG["flash_blocks"]
    (numbers,) = _segments(jnp.asarray(batch[:, :-1]), family.model_config(CONFIG))
    numbers = np.asarray(numbers)
    S = numbers.shape[1]
    under, _ = block_counts(S, blk_q, blk_k)
    live = sum(block_counts(S, blk_q, blk_k, None, row)[0] for row in numbers)
    assert 0 < live < len(numbers) * under
    assert family.dead_block_share(CONFIG, [batch]) == pytest.approx(
        1 - live / (len(numbers) * under), abs=1e-12)


def test_what_keeps_no_documents_apart_is_refused():
    base = dict(end_of_document=0, attn_core="flash")
    fc.refused("packed documents", **{**base, "attn_core": "dense"})
    fc.refused("packed documents", mixer="gated_delta", delta_heads=(2, 4, 16),
               **base)
    fc.refused("packed documents", mtp_depth=1, **base)
    fc.refused("flash core", attention_multiplier=0.5)  # on the dense core
    mc = family.model_config(CONFIG)
    assert dataclasses.replace(mc, end_of_document=None).stacks[1][0].mixer == "attention"
