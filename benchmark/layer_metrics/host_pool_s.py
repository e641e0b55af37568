"""Input: the host pool of `traffic["pool"]` batches made from the seed and
the placement built: the marks `t_placed` to `t_pool`. Host clock,
seconds."""


def read(record, trace):
    return record["marks"]["t_pool"] - record["marks"]["t_placed"]
