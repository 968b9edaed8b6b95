"""batch_gather_ms.<cell kind>: the clients' batch draws and gathers
(``ClientDataset.batches`` / ``batch``), the self time of the engine's
``gather`` spans in the window, in ms per round."""
import program_spans


def read(run):
    return program_spans.ms_per_round(run, ("gather",))
