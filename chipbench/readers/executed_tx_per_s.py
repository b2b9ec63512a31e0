"""Transactions executed on every validator inside the window, over the window."""


def read(obs):
    return obs["executed_in_window"] / obs["seconds"]
