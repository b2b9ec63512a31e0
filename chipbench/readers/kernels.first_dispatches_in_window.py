"""First dispatches of a (kernel, shapes) pair, each a jit trace and a compile
or a cache load, that ran inside the window (the `compile` records whose
[t - wall_s, t] meets it)."""

from chipbench.readers import flight_window as fw


def read(obs):
    win = fw.window(obs)
    if win is None:
        return None
    return float(sum(1 for c in win.by["compile"] if c.t - c.wall_s <= win.t1 and c.t >= win.t0))
