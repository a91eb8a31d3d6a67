"""host_ms.sweep: mean milliseconds per sweep of the program span
netsim.sweep less its child netsim.sweep.run (dispatch until the outputs
are ready): the sweep's own host work of assembly, upload and decode."""
from bench import program


def read(run):
    sweeps = program.spans(run, "netsim.sweep")
    ran = {s.parent: s.seconds for s in program.spans(run, "netsim.sweep.run")}
    if not sweeps:
        return None
    return 1000.0 * sum(s.seconds - ran.get(s.id, 0.0)
                        for s in sweeps) / len(sweeps)
