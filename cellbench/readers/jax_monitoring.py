"""Reader: seconds XLA spent compiling or loading programs, summed
over the rank-threads of the app shell
(``/jax/core/compile/backend_compile_duration`` through
``jax.monitoring``, as examples/device_smoke.py reads it).  A cell's
first run in a checkout compiles; every later one loads from the
persistent cache."""


def read(spec: dict, facts: dict, say):
    return facts["compile_or_load_s"] or None
