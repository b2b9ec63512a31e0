"""Of the drains in the window, the share that began with a new drainer task
(`WireStats.drainer_starts` over `WireStats.drains`): 1 where every burst
finds no drainer running and starts one, lower where a drainer lives on
through `drain()` and takes the next burst too. None on a program that does
not count the starts."""


def read(obs):
    wire = obs["window"]["wire"]
    return wire["drainer_starts"] / wire["drains"] if "drainer_starts" in wire and wire["drains"] else None
