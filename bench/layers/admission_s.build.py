"""admission_s.build: mean seconds of RoutedPod.timings["at_s"] over the
window's builds."""


def read(run):
    t = [o["timings"]["at_s"] for o in run.outputs if o is not None]
    return sum(t) / len(t) if t else None
