"""95th percentile of how late the event loop ran its 20 ms heartbeat, over
every wake in the window, all loops. Late wakes are records of their own; the
quiet ones come as a count and a sum, and stand at their mean if the
percentile falls among them."""

from chipbench.readers import flight_window as fw


def read(obs):
    win = fw.window(obs)
    if win is None:
        return None
    lags = fw.within(win, "lag", "woke")
    late = sorted(r.woke - r.due for r in lags if r.woke - r.due > win.period)
    quiet = sum(r.quiet for r in lags)
    wakes = quiet + len(late)
    if not wakes:
        return None
    rank = min(wakes - 1, int(0.95 * wakes))
    if rank < quiet:
        return 1000.0 * sum(r.quiet_sum for r in lags) / quiet
    return 1000.0 * late[rank - quiet]
