"""Launcher: command start -> the reporting rank sees `jax.devices()` of the
whole world (after `initialize_device_plane()` under kfrun; backend up in a
one-process cell), less the backend's own start
(`end_to_end.backend_start_s`), which is the machine's and which `setup_s`
leaves out too. Until PR 37 it read the whole `t_world - t_command`; since
PR 38 it is the part of it that `setup_s` holds, so that this,
`state_init_s`, `state_place_s`, `host_pool_s`, `first_step_s` and the nine
warm-up and probe steps sum to `setup_s`. Host clock, seconds."""

from benchmark.end_to_end import backend_start_s


def read(record, trace):
    marks = record["marks"]
    return marks["t_world"] - marks["t_command"] - backend_start_s(record)
