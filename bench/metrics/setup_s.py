"""Set-up: process start to the first timed request — data from the seed,
plan builds, compiles and warm-up."""


def read(run):
    return run.setup_s
