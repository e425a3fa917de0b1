"""Share of the chunk program's device time in the traced part that its
``exchange`` ops take (owner bucketing, the forward and reverse
all-to-all, the result scatter): leaf ops only (a ``while``,
``conditional`` or ``call`` op's time is its body's), told apart by the
program's own op -> phase table in the span file
(``benchlib/optable.py``) and placed in the chunk program by its ``XLA
Modules`` executions, summed over the chips.  Nothing where the program
has no exchange (one chip)."""
from benchlib.optable import op_phases, phase_seconds


def read(rec):
    by_phase = phase_seconds(rec, op_phases(rec.cell))
    if not by_phase or "exchange" not in by_phase:
        return None
    return by_phase["exchange"] / sum(by_phase.values()) * 100.0
