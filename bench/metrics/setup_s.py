"""Set-up: process start to the first measured moment (loading, weights,
warm-up and any compilation)."""


def read(run, peaks):
    return run.setup_s
