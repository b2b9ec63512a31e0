"""Mean time of one call into the ordering engine (`t_done - t_start` of the
`walk` records started in the window), insert-only calls included."""

from chipbench.readers import flight_window as fw


def read(obs):
    walks = fw.within(fw.window(obs), "walk", "t_start")
    return 1000.0 * sum(w.t_done - w.t_start for w in walks) / len(walks) if walks else None
