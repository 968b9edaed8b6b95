"""dispatch_ms.<cell kind>: the launch as the host sees it, the self
time of the engine's ``apply`` spans in the window, in ms per round. On
one chip it holds the copy of the host arguments to the device."""
import program_spans


def read(run):
    return program_spans.ms_per_round(run, ("apply",))
