"""Timed events / (first timed batch emitted -> env.execute() returned):
all the work over all the time, every chip of the cell together. The clock
stops only when every window of the timed events has reached the sink."""


def measure(run):
    if run.traffic["pacing"] != "unthrottled":
        return None          # a paced cell offers a rate; it does not find one
    return run.timed_events / run.window_s
