"""Host batch -> device batch by `jax.make_array_from_callback`: every
process gives the rows of the global batch that its own chips hold, the one
way to build an array that spans the processes of a joined world."""


def make(mesh, axis: str):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(axis))
    return lambda batch: jax.tree.map(
        lambda x: jax.make_array_from_callback(
            x.shape, sharding, lambda idx: x[idx]), batch)
