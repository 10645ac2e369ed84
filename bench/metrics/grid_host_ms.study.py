"""Host time per study that no device work covers (ms), median over the
window's studies: the `repro.study` span (the whole `run_cohort_grid`
call) minus the union, over chips, of its `repro.sweep.device` stamps;
lane operands, dispatch and gather left uncovered."""
import _spans


def read(run):
    window = _spans.units("repro.study", len(run["loop"].done))
    if window is None:
        return None
    return _spans.median([_spans.uncovered_ms(study, under)
                          for study, under in window
                          if any(r.name == _spans.DEVICE for r in under)])
