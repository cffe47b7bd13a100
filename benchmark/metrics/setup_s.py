"""Process start to window start: imports, backend, data, warm-up and,
in a run that compiles, compilation."""


def read(run):
    return run["setup_s"]
