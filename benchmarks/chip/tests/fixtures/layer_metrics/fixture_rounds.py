"""A per-layer metric that exists only as a fixture file: session rounds
inside the window. It shows that a new metric needs only its reader."""


def read(run):
    return float(run.rounds) if run.rounds else None
