"""Reader: a number the run already holds (``field`` names it in the
run's facts), as it stands: ``iter_us`` of a traced run is what an
iteration takes with the library's tracing and the profiler on, beside
the timed run's ``iter_us``.  A field the run does not hold gives
nothing."""


def read(spec: dict, facts: dict, say):
    v = facts.get(spec["field"])
    return None if v is None else float(v)
