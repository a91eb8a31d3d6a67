"""bfs_s.build: mean seconds per build of the program span
routing.select.bfs (phase 0 of sharded selection: the state BFS of every
shard, with its unique-path pass) in the window."""
from bench import program


def read(run):
    return program.seconds_per(run, "routing.select.bfs",
                               "pipeline.route_pod")
