"""upload_host_ms.<cell kind>: what each upload costs the host in the
program's upload loop (``core/cohort.py::fold_upload``), the self time
of its ``stage`` (the batch placed on the device), ``contribute`` (the
step dispatched) and ``host_sync`` (the acknowledgement fetched, which
waits for the device) spans in the window, in ms per upload. Divided by
the uploads whose ``contribute`` span the window holds: with a few
uploads a window, the first one's spans start before it."""
import program_spans

SPANS = ("stage", "contribute", "host_sync")


def read(run):
    events = program_spans.window(run)
    if events is None:
        return None
    uploads = sum(ev["name"] == "contribute" for ev in events)
    if not uploads:
        return None
    st = program_spans.self_times(events)
    return sum(st.get(n, 0.0) for n in SPANS) / 1e3 / uploads
