"""Set-up: process start to the first arrival, compilation included."""


def read(run):
    return run["setup_s"]
