"""Frames every link of the committee sent in the window per drain its
drainers made (`WireStats.frames_sent` over `WireStats.drains`): how many
frames a turn of a connection's drainer writes before its one flush."""


def read(obs):
    wire = obs["window"]["wire"]
    return wire["frames_sent"] / wire["drains"] if wire.get("drains") else None
