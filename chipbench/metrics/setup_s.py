"""Process start to the first timed request: imports, the population,
the one-shot round, the session, the request pool and the warm-up
(compilation, or loading from the compile cache), on the host clock."""
SOURCE = "host_clock"


def read(rec):
    return rec.setup_s
