"""Process start to the start of the measured window: imports, build,
weights, warm start, compiles or cache loads, the checked first steps and
the warm-up."""


def read(f):
    return f["setup_s"]
