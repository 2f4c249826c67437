"""Process start to the first timed request: graph, service, builds,
compiles and warm-up."""


def read(run):
    return run.setup_s
