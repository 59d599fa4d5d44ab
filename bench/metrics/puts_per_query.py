"""puts_per_query: arrays moved to the device per query, the ``arrays``
count of the program's ``regate.put`` spans. Nothing is returned where
the program opens no such span."""
from bench import program_spans


def read(red: dict):
    return program_spans.count_per_query(red, "regate.put", "arrays")
