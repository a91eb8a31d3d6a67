"""recover_p90_s: the 90th percentile of the wall seconds of every
repair in the window."""
import statistics


def read(run):
    if len(run.unit_s) < 2:
        return None
    return statistics.quantiles(run.unit_s, n=10, method="inclusive")[8]
