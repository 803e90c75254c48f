"""Reader: the host's wall clock across the launch.  ``from`` and
``to`` name two instants the run records: ``t0_epoch`` (first
statement of cellbench/run.py), ``rank_main_epoch`` (first statement
rank 0 reaches in cellbench/rank.py), ``wall_open`` (the window
opens)."""


def read(spec: dict, facts: dict, say):
    return facts[spec["to"]] - facts[spec["from"]]
