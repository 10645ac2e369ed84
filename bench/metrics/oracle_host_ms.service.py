"""Host time inside the window oracle per control tick (ms), median over
the window's ticks: each tick's `repro.service.oracle` span minus the part
its `repro.sweep.device` stamps cover (lane arrays, chunk gathers,
dispatch, transfers and budget checks)."""
import _spans


def read(run):
    n = sum(int(d["n_ticks"]) for d in run["loop"].done)
    window = _spans.units("repro.service.tick", n)
    if window is None:
        return None
    out = []
    for _, under in window:
        oracle = [r for r in under if r.name == "repro.service.oracle"]
        if oracle and any(r.name == _spans.DEVICE for r in under):
            out.append(_spans.uncovered_ms(oracle[0], under))
    return _spans.median(out)
