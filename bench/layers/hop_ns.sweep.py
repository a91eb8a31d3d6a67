"""hop_ns.sweep: device nanoseconds of the sweep kernels per packet-hop:
the device time of the programs that netsim.KERNEL_PROGRAMS names, in
the traced window, over the packet-hops (the program counter
netsim.sweep.hops) of the window's sweeps."""
from bench import program


def read(run):
    if run.trace is None:
        return None
    try:
        from repro.core.netsim import KERNEL_PROGRAMS
    except ImportError:
        return None
    t = sum(s for name, s in run.trace["programs"].items()
            if name.split("(")[0] in KERNEL_PROGRAMS)
    hops = sum(program.counts(run, "netsim.sweep.hops"))
    return 1e9 * t / hops if t > 0 and hops else None
