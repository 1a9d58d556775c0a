"""Host time of a round outside the program's device step: the window's
mean of (round interval - the trainer's own ``step_s``), in ms.  It is
the loader, the scalar syncs and the ledger's pricing that the trainer
loop does around each step."""
import numpy as np


def read(run):
    c = run.counters
    if not c.get("round_s") or len(c["round_s"]) != len(c.get("step_s", ())):
        return None
    return float(np.mean(np.subtract(c["round_s"], c["step_s"])) * 1e3)
