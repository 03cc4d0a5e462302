"""d2h_ms (ms, program span): mean per batch of the copy of a batch's
ready outputs to the host (runtime/engine.py _retire, span engine.copy,
report() phase copy).  Read for every ``d2h_ms.<suffix>``; None from a
program without the phase."""
from harness.stats import engine_phase_ms


def read(run):
    return engine_phase_ms(run, "copy")
