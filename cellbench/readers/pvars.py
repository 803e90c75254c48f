"""Reader: the library's MPI_T performance variables, read by every
run before and after the window (between host barriers).  The value is
the variable's delta over the window per rank-iteration; a variable
that did not move gives nothing."""


def read(spec: dict, facts: dict, say):
    name = spec["pvar"]
    delta = facts["pvars_after"].get(name, 0) \
        - facts["pvars_before"].get(name, 0)
    if not delta:
        return None
    return delta / (facts["iters"] * facts["ranks"])
