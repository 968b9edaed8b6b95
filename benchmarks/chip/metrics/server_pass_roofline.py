"""server_pass_roofline: the least time of a round's eq. 3 + 5 traffic,
(2K + 2) * Np * 4 bytes over the chip's HBM bandwidth, as a share of the
device-busy time per round in the trace. It counts the same work
whatever kernel or fusion runs it."""


def read(run):
    t = run["trace"]
    rounds = run["counts"]["rounds"]
    if t is None or not rounds or t.busy_s <= 0:
        return None
    least_s = run["work"]["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (t.busy_s / rounds)
