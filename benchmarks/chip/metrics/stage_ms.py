"""stage_ms.<cell kind>: stacking each launch's chunk arguments, the
self time of the engine's ``stage`` spans in the window, in ms per
round."""
import program_spans


def read(run):
    return program_spans.ms_per_round(run, ("stage",))
