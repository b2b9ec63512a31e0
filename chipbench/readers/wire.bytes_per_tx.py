"""Bytes every link of the committee sent per executed transaction."""


def read(obs):
    return obs["window"]["wire"]["bytes_sent"] / obs["executed_in_window"] if obs["executed_in_window"] else None
