"""Transactions the workers refused (RESOURCE_EXHAUSTED) over those offered, in per cent."""


def read(obs):
    return 100.0 * obs["shed_tx"] / obs["attempted"] if obs["attempted"] else None
