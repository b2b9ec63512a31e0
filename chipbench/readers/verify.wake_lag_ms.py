"""Mean time from a flush's verdicts posted to the waiting coroutine resumed,
per entry (the `wake` records of the flushes posted in the window)."""

from chipbench.readers import flight_window as fw


def read(obs):
    wakes = fw.within(fw.window(obs), "wake", "t_posted")
    count = sum(w.entries for w in wakes)
    return 1000.0 * sum(w.lag_sum for w in wakes) / count if count else None
