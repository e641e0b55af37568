"""Model: of the causal (query, key) pairs of a packed row, the share that lie
within one document, the mean over the run's pool of host batches, in %: what
fraction of a full causal sweep the attention needs. The count is the
benchmark's own over the batches made again from `record["seed"]`
(`families.granite_hybrid.pool_within_document_pairs`, numpy: the process that
reads a record imports no jax; of a record that names no seed, the
expectation under the configuration's documents);
`kungfu_tpu.models.transformer.packing_stats` is the program's account of the
same rows, and `tests/benchmark/test_bench_granite_hybrid.py` holds the two
together."""

from benchmark.families import granite_hybrid


def read(record, trace):
    cfg = granite_hybrid.cell_config(record)
    if "documents" not in cfg:  # a record of another configuration's batches
        return None
    return (100.0 * granite_hybrid.pool_within_document_pairs(record)
            / granite_hybrid.causal_pairs(cfg))
