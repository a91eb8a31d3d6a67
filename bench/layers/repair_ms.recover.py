"""repair_ms.recover: mean milliseconds per repair of the program span
repair.repair_fault in the window; the probe that follows each repair
lies outside it."""
from bench import program


def read(run):
    s = program.seconds_per(run, "repair.repair_fault",
                            "repair.repair_fault")
    return None if s is None else 1000.0 * s
