"""Mean time of a flush from its seal to its verdicts handed to the waiters'
loops (`t_posted - t_seal`), flushes sealed in the window."""

from chipbench.readers import flight_window as fw


def read(obs):
    flushes = fw.within(fw.window(obs), "flush", "t_seal")
    return 1000.0 * sum(f.t_posted - f.t_seal for f in flushes) / len(flushes) if flushes else None
