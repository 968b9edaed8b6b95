"""launches_per_round.<cell kind>: the engine's own dispatch count
(SimResult.num_launches) over the rounds it completed in the window.
An exact count."""


def read(run):
    c = run["counts"]
    return c["launches"] / c["rounds"] if c.get("rounds") else None
