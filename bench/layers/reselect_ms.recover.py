"""reselect_ms.recover: mean milliseconds per repair of its re-selection
stages (walk, BFS refresh, re-admission, greedy, refine), from the stage
timings that repair_fault reports."""

STAGES = ("walk_s", "bfs_s", "readmit_s", "greedy_s", "refine_s")


def read(run):
    t = [sum(o["stats"][k] for k in STAGES)
         for o in run.outputs if o is not None]
    return 1000.0 * sum(t) / len(t) if t else None
