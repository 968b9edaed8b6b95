"""engine_call_ms.<cell kind>: what each engine call costs the host
around its launches, the self time of the ``prepare`` (state and ring
restored, statics and pool placed), ``host_sync`` (the round log
fetched, which waits for the launches the host ran ahead with, and its
rows built) and ``checkpoint`` (state captured to the host) spans in
the window, in ms per round."""
import program_spans

SPANS = ("prepare", "host_sync", "checkpoint")


def read(run):
    return program_spans.ms_per_round(run, SPANS)
