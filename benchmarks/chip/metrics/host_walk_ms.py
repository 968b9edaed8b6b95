"""host_walk_ms.<cell kind>: the host event walk (heap pops, behaviour
draws, reschedules), the self time of the engine's ``collect_window``
spans in the window, in ms per round."""
import program_spans


def read(run):
    return program_spans.ms_per_round(run, ("collect_window",))
