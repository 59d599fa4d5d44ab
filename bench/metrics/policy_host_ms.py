"""policy_host_ms: host milliseconds per query inside the policy
engine's jax path (the program's ``regate.evaluate_batch`` spans) while
no operation ran on the device. In the sweep it is the call less the
entry point's own work; in the plane, the policy side of the call.
Nothing is returned where the program opens no such span."""
from bench import program_spans


def read(red: dict):
    return program_spans.host_ms_per_query(red, ("regate.evaluate_batch",))
