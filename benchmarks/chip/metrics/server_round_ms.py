"""server_round_ms: the window's wall time over the rounds completed in
it (host clock). Each round ends in block_until_ready."""


def read(run):
    return run["seconds"] / run["counts"]["rounds"] * 1e3
