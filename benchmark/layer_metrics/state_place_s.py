"""Launcher: `world.place_state` (under kfrun `broadcast_variables`: rank
0's state to every worker, then onto the mesh; `replicate` in one process)
and `factory.place` with the optimizer's state, closed by block_until_ready:
the marks `t_init` to `t_placed` on the reporting rank. Host clock,
seconds."""


def read(record, trace):
    return record["marks"]["t_placed"] - record["marks"]["t_init"]
