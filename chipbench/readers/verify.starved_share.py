"""Share of the window in which no flush is in flight and both lanes of the
verify service are empty: the device waits for the protocol plane."""

from chipbench.readers import flight_window as fw


def read(obs):
    shares = fw.verify_shares(fw.window(obs))
    return shares[0] if shares else None
