"""Fill of the EASY candidate passes in the window's studies, in percent:
the candidate trips that tested a waiting job (`backfill_candidates`) over
the trips run (`backfill_slots`: `backfill_depth` per pass), summed over
the dispatches of the window's `repro.baselines` spans. The rest are trips
of the fixed-length pass that test no job."""
import _spans


def read(run):
    window = _spans.units("repro.baselines", len(run["loop"].done))
    ds = [r for _, under in window or () for r in under
          if r.name == _spans.DISPATCH and "backfill_slots" in r.counts]
    slots = sum(d.counts["backfill_slots"] for d in ds)
    if not slots:
        return None
    return sum(d.counts.get("backfill_candidates", 0) for d in ds) \
        / slots * 100.0
