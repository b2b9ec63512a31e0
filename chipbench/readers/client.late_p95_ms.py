"""How late the generator sent its bursts (sent - due), 95th percentile."""


def read(obs):
    return obs["late"].get("p95")
