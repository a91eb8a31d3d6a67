"""kernel_ms.sweep: device milliseconds of the sweep kernel per sweep, from
the trace."""


def read(run):
    if run.trace is None:
        return None
    t = sum(s for name, s in run.trace["programs"].items()
            if "_sweep_csr" in name)
    n = len(run.spans.seconds("sweep"))
    return 1000.0 * t / n if t > 0 and n else None
