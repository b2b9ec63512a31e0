"""Trips metric-naming: names off the <subsystem>_<name>[_<unit>] grammar."""


def build(registry):
    # Not snake_case: double underscore.
    bad_case = registry.counter("worker__txReceived", "camel/double underscore")
    # Unknown subsystem prefix.
    bad_subsystem = registry.gauge("widget_queue_depth", "no such subsystem")
    # Histogram without a unit suffix.
    bad_unit = registry.histogram("primary_propose_latency", "missing unit")
    return bad_case, bad_subsystem, bad_unit
