"""Share of the window in which no flush is in flight while an entry is queued
or being packed: the device waits for the seal deadline and the packing."""

from chipbench.readers import flight_window as fw


def read(obs):
    shares = fw.verify_shares(fw.window(obs))
    return shares[1] if shares else None
