"""Host time per control tick outside the window oracle (ms), median over
the ticks of the traced window: the benchmark's tick time minus the
program's own `oracle_ms` for that tick (window slice, pack, monitor,
decide and scoring)."""
import numpy as np


def read(run):
    host = []
    for rec in run["loop"].done:
        if len(rec["tick_ms"]) != len(rec["oracle_ms"]):
            continue
        host.extend(rec["tick_ms"] - rec["oracle_ms"])
    return float(np.median(host)) if host else None
