"""Launcher: the seconds the reporting rank spent importing the program's
packages before the world stood: the program's `worker.import` spans
(`kungfu_tpu/__init__.py` and `kungfu_tpu/parallel/__init__.py` over their
own bodies, first line to last; the second brings jax and optax), merged,
before the mark `t_world`. What every restarted worker of a reload-mode
resize pays again first. 0 where the ring holds no such span. Program span,
seconds.

The readers of the runtime's own spans (this one, `state_init_*`,
`peer_compile_miss_s`, `replicate_compile_s`, `gc_pause_*`) give a number
wherever a traced run asks them, 0 where the ring holds no span of theirs:
a hook that broke reads 0 on the chip, not nothing. An untraced record is
not a per-layer metric's to read, and gets None."""

from benchmark.trace_reduce import clip, length


def ring(spans, name: str, where=lambda args: True) -> list:
    """The [start, end] of the spans `[name, start, end, depth, args]`
    whose name starts with `name` and whose args pass `where`."""
    return [s[1:3] for s in spans if s[0].startswith(name) and where(s[4])]


def read(record, trace):
    if not record["traced"]:
        return None
    mine = ring(record["spans"], "worker.import")
    return float(length(clip(mine, float("-inf"), record["marks"]["t_world"])))
