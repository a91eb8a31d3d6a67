"""traffic_ms.sweep: mean milliseconds of compile_flow_traffic per sweep,
from the benchmark's span."""


def read(run):
    s = run.spans.seconds("traffic")
    return 1000.0 * sum(s) / len(s) if s else None
