"""walk_s.build: mean seconds per build of the program spans
routing.select.walk (the candidate walks of every round over every
shard) in the window."""
from bench import program


def read(run):
    return program.seconds_per(run, "routing.select.walk",
                               "pipeline.route_pod")
