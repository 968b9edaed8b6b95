"""setup_s: process start to the window's start (host clock): imports,
reaching the chip, inputs, weights and every compile or cache load."""


def read(run):
    return run["setup_s"]
