"""Median, over every transaction due in the window, of due -> executed on the last validator."""


def read(obs):
    return obs["latency"].get("p50")
