"""Device idle share of the traced window, in percent: 1 - (union of device
op intervals, summed over chips) / (window x chips)."""
import _device


def read(run):
    return _device.idle_pct(run)
