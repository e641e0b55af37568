"""Train step: host clock around the first step (lower, compile or load
from the compile cache, run), closed by block_until_ready. Seconds."""


def read(record, trace):
    return record["first_step_s"]
