"""Train step: the end of the first step to the start of the window: the
step program's memory account, the three warm-up steps, the six probe steps
and the world's agreement on the window's step count: the marks `t_first_1`
to `t_window`. With `launch_to_world_s`, `state_init_s`, `state_place_s`,
`host_pool_s` and `first_step_s` it makes `setup_s`. Host clock, seconds."""


def read(record, trace):
    return record["marks"]["t_window"] - record["marks"]["t_first_1"]
