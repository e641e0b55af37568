"""Device: what the step program holds in HBM while it runs, from
`compiled.memory_analysis()`: arguments + outputs + temporaries - aliased
(donated arguments that become outputs). `memory_stats()` does not see a
program's temporaries (PERF.md). GB = 1e9 bytes."""


def read(record, trace):
    return record["program_memory"]["total_bytes"] / 1e9
