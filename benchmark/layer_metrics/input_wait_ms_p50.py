"""Input: the benchmark's span around "take the next host batch and place
it", median over the window's steps. Host clock, milliseconds."""

from benchmark.trace_reduce import median


def read(record, trace):
    spans = [b - a for name, a, b in record["window"]["spans"]
             if name == "bench.input"]
    return median(spans) * 1e3 if spans else None
