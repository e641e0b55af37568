"""Launcher: command start -> the reporting rank sees `jax.devices()` of the
whole world (after `initialize_device_plane()` under kfrun; backend up in a
one-process cell). Host clock, seconds."""


def read(record, trace):
    return record["t_world"] - record["t_command"]
