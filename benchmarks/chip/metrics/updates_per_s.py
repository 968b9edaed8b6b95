"""updates_per_s[.<cell kind>]: client updates folded into the model
over the window's wall time (host clock): K times the rounds
completed."""


def read(run):
    return run["counts"]["updates"] / run["seconds"]
