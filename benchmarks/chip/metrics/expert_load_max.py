"""expert_load_max.<cell kind>: how unevenly the router loads the experts
this chip holds. For each local step and MoE layer, the routed pairs of
the most-loaded held expert over the held experts' mean; averaged over
the steps of the uploads in the window. The counts are the program's,
recorded by its upload loop as the ``expert_counts`` arg (steps x
layers x held experts) of each acknowledgement's ``host_sync`` span.
1 is an even load; a chip waits for its busiest expert."""
import numpy as np

import program_spans


def read(run):
    events = program_spans.window(run)
    if events is None:
        return None
    ratios = []
    for ev in events:
        counts = ev.get("args", {}).get("expert_counts")
        if ev["name"] != "host_sync" or counts is None:
            continue
        c = np.asarray(counts, np.float64)
        c = c.reshape(-1, c.shape[-1])  # (steps x layers, held)
        mean = c.mean(axis=-1)
        ratios += list(c.max(axis=-1)[mean > 0] / mean[mean > 0])
    return float(np.mean(ratios)) if ratios else None
