"""Write-ahead-log flushes (fused commit groups, every store of the committee)
in the window per transaction executed in it."""

from chipbench.readers import flight_window as fw


def read(obs):
    win = fw.window(obs)
    if win is None or not obs["executed_in_window"]:
        return None
    return len(fw.within(win, "wal_flush", "t")) / obs["executed_in_window"]
