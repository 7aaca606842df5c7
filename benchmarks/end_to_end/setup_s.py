"""Process start -> first timed event: loading, compiling (or finding the
programs in the cache), the prefill of every key, the warm phase."""


def measure(run):
    return run.setup_s
