"""Process start to the first measured request: loading, making the
weights, compiling (or loading compiled programs), warm-up traffic."""


def read(run):
    return run["setup_s"]
