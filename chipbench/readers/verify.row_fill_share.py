"""Useful rows over the rows handed to the device, padding included, in per
cent: flushes sealed in the window, both lanes."""

from chipbench.readers import flight_window as fw


def read(obs):
    flushes = fw.within(fw.window(obs), "flush", "t_seal")
    padded = sum(f.padded for f in flushes)
    return 100.0 * sum(f.useful for f in flushes) / padded if padded else None
