"""ep_trace_ms.sweep: mean milliseconds per call of the program span
workload.ep_trace, which routes one expert-parallel decode step and
builds its phased demand (one call per unit of the replay cells)."""
from bench import program


def read(run):
    calls = program.spans(run, "workload.ep_trace")
    return 1000.0 * sum(s.seconds for s in calls) / len(calls) \
        if calls else None
