"""Stream time-steps completed per second, summed over the sessions, over
the measured span (whole update windows, host clock)."""


def read(ctx):
    w = ctx["window"]
    return w["steps"] / w["span_s"]
