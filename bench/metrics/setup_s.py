"""Process start to the window's start: data, ingest, warm-up, compiles."""


def read(run):
    return run.setup_s
