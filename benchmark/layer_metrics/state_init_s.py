"""Model: `family.init(config, seed)` on the reporting rank, the state made
from the seed, compile or cache load of the init program included, closed by
block_until_ready: the marks `t_world` to `t_init` (building the step
function, which traces nothing yet, is in it). Host clock, seconds."""


def read(record, trace):
    return record["marks"]["t_init"] - record["marks"]["t_world"]
