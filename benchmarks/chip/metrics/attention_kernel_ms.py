"""attention_kernel_ms.<cell kind>: the causal attention kernel's device
time per upload, the self time of the device ops named
``splash_mha_<fwd|dq|dkv>... custom-call`` in the trace (JAX's block-sparse
attention kernel names its calls by pass), over the uploads completed.
None where no such op ran: a program without the kernel, or one whose
attention took the chunked XLA path."""
import re

KERNEL = re.compile(r"^splash_mha_(fwd|dq|dkv)\w*(\.\d+)? custom-call$")


def read(run):
    t = run["trace"]
    uploads = run["counts"]["rounds"]
    if t is None or not uploads:
        return None
    kernel_s = sum(s for name, s in t.op_seconds.items()
                   if KERNEL.match(name))
    if kernel_s <= 0:
        return None
    return 1e3 * kernel_s / uploads
