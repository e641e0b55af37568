"""The operation counts `mfu_pct` divides by, against hand counts, and the
seeded batches."""

import json
import os

import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark.families import resnet, transformer


def _config(name):
    with open(os.path.join(mf.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


BERT = _config("bert_base")
RESNET50 = _config("resnet50")


def test_bert_base_matmul_parameters_by_hand():
    # a layer: 768x2304 + 768x768 + 768x3072 + 3072x768 = 7,077,888
    # twelve of them: 84,934,656; the tied head 30,522x768 = 23,440,896
    assert transformer.matmul_params(BERT) == 84_934_656 + 23_440_896


def test_bert_base_flops_per_sample_by_hand():
    # a token, forward: 2 x 108,375,552 in matmuls, and causal attention
    # 2 x 512 x 768 in each of 12 layers (QK^T and PV over half the keys)
    forward = 2 * 108_375_552 + 12 * 2 * 512 * 768
    assert forward == 226_188_288
    assert transformer.flops_per_sample(BERT) == 3 * forward * 512
    assert round(transformer.flops_per_sample(BERT) / 1e9, 1) == 347.4


@pytest.mark.parametrize("key,factor", [
    ("num_hidden_layers", 2), ("vocab_size", 2), ("max_position_embeddings", 2)])
def test_transformer_flops_scale_with_their_shapes(key, factor):
    bigger = dict(BERT, **{key: BERT[key] * factor})
    d, s, layers = 768, 512, 12
    per_layer = 2 * 7_077_888 + 2 * s * d
    head = 2 * 30_522 * d
    if key == "num_hidden_layers":
        want = 3 * (2 * layers * per_layer + head) * s
    elif key == "vocab_size":
        want = 3 * (layers * per_layer + 2 * head) * s
    else:  # twice the positions: twice the tokens, twice the keys a query
        want = 3 * (layers * (2 * 7_077_888 + 2 * 2 * s * d) + head) * 2 * s
    assert transformer.flops_per_sample(bigger) == want


# ResNet-50 at 224 x 224, multiply-adds by hand (v1.5: a down-sampling
# block's first 1x1 runs before the stride).
STEM = 112 * 112 * 49 * 3 * 64
STAGE1 = (56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
          + 2 * 56 * 56 * (256 * 64 + 9 * 64 * 64 + 64 * 256))
STAGE2 = (56 * 56 * 256 * 128 + 28 * 28 * (9 * 128 * 128 + 128 * 512 + 256 * 512)
          + 3 * 28 * 28 * (512 * 128 + 9 * 128 * 128 + 128 * 512))
STAGE3 = (28 * 28 * 512 * 256 + 14 * 14 * (9 * 256 * 256 + 256 * 1024 + 512 * 1024)
          + 5 * 14 * 14 * (1024 * 256 + 9 * 256 * 256 + 256 * 1024))
STAGE4 = (14 * 14 * 1024 * 512 + 7 * 7 * (9 * 512 * 512 + 512 * 2048 + 1024 * 2048)
          + 2 * 7 * 7 * (2048 * 512 + 9 * 512 * 512 + 512 * 2048))
HEAD = 2048 * 1000


def test_resnet50_multiply_adds_by_hand():
    want = STEM + STAGE1 + STAGE2 + STAGE3 + STAGE4 + HEAD
    assert resnet.conv_macs(RESNET50) == want
    assert round(want / 1e9, 2) == 4.09  # the figure every model zoo quotes


def test_resnet50_flops_per_sample():
    assert resnet.flops_per_sample(RESNET50) == 6 * resnet.conv_macs(RESNET50)
    assert round(resnet.flops_per_sample(RESNET50) / 1e9, 1) == 24.5


def test_resnet_stem_and_head_alone():
    none = dict(RESNET50, stage_sizes=[])
    assert resnet.conv_macs(none) == STEM + 64 * 1000


@pytest.mark.parametrize("family,config", [
    (transformer, dict(BERT, max_position_embeddings=16, vocab_size=100)),
    (resnet, dict(RESNET50, image_size=16)),
])
def test_host_batches_come_from_the_seed(family, config):
    a = family.host_batch(config, 7, 3, 4)
    b = family.host_batch(config, 7, 3, 4)
    c = family.host_batch(config, 7, 4, 4)
    d = family.host_batch(config, 8, 3, 4)
    first = lambda x: np.asarray(x[0] if isinstance(x, tuple) else x, np.float32)
    assert np.array_equal(first(a), first(b))
    assert not np.array_equal(first(a), first(c))
    assert not np.array_equal(first(a), first(d))
    assert len(first(a)) == 4


def test_transformer_batch_shape_and_range():
    ids = transformer.host_batch(BERT, 1, 0, 3)
    assert ids.shape == (3, 513) and ids.dtype == np.int32
    assert ids.min() >= 0 and ids.max() < 30_522


def test_resnet_batch_shape_and_types():
    images, labels = resnet.host_batch(RESNET50, 1, 0, 2)
    assert images.shape == (2, 224, 224, 3) and images.dtype.name == "bfloat16"
    assert images.nbytes == 2 * 224 * 224 * 3 * 2
    assert labels.dtype == np.int32 and 0 <= labels.min() and labels.max() < 100
