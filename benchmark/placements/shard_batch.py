"""Host batch -> device batch through the program's own
`parallel.dp.shard_batch`, sharded over the step's batch axis."""


def make(mesh, axis: str):
    from kungfu_tpu.parallel.dp import shard_batch

    return lambda batch: shard_batch(batch, mesh, axis)
