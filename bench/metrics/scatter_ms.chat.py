"""Admission prefill: device time of the slot scatter program
(``jit_slot_scatter``, which writes each round's prefilled rows into the
cache) per admission round in the window, in ms: device trace.  None where
the program's scatter has no name of its own."""
from bench.lib import readings

PROGRAM = "jit_slot_scatter"


def read(run):
    s = run.trace.module_s.get(PROGRAM) if run.trace else None
    adm = run.window.admissions
    if s is None or not adm:
        return None
    return s * readings.MS / len(adm)
