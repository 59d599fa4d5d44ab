"""sweep_ns_per_row: device nanoseconds of the sweep kernel's XLA
program per op row and knob point it evaluates: its time in the device
trace over the sum of ``ops`` x ``knobs`` that the program's
``regate.harvest`` span carries for each sweep-kernel call. It compares
the kernel's cost per unit of work across op mixes. Nothing is returned
where the kernel did not run or the program counts no rows."""
from bench import program_spans


def read(red: dict):
    t = red["kernel_ns"].get("sweep_kernel")
    work = sum(st["ops"] * st["knobs"]
               for st in program_spans.stats_of(red, "regate.harvest")
               if "ops" in st)
    if not t or not work:
        return None
    return t / work
