"""put_ms: host milliseconds per query spent moving arrays to the device
(the program's ``regate.put`` spans: ``policies._backend_data``'s host
columns, ``_knob_arrays`` and the event-scan kernel's input in
``program_plane._run_kernel``). Nothing is returned where the program
opens no such span."""
from bench import program_spans


def read(red: dict):
    return program_spans.ms_per_query(red, "regate.put")
