"""Mean wait of a verification in the shared service's queue: enqueued -> its
flush sealed, summed over the entries of the flushes sealed in the window."""

from chipbench.readers import flight_window as fw


def read(obs):
    flushes = fw.within(fw.window(obs), "flush", "t_seal")
    entries = sum(f.entries for f in flushes)
    return 1000.0 * sum(f.wait_sum for f in flushes) / entries if entries else None
