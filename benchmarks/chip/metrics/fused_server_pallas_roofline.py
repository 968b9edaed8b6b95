"""fused_server_pallas_roofline: the fused server-pass kernel's share of
its HBM roofline. Its least time a round is (2K + 2) * Np * 4 bytes over
the chip's HBM bandwidth; its time a round is the self time of the
device ops named ``fused_server_pallas.<n> custom-call`` in the trace
(the kernel's scope names its call), over the rounds completed."""
import re

KERNEL = re.compile(r"^fused_server_pallas(\.\d+)? custom-call$")


def read(run):
    t = run["trace"]
    rounds = run["counts"]["rounds"]
    if t is None or not rounds:
        return None
    kernel_s = sum(s for name, s in t.op_seconds.items()
                   if KERNEL.match(name))
    if kernel_s <= 0:
        return None
    least_s = run["work"]["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (kernel_s / rounds)
