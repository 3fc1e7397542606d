"""Output tokens delivered inside the window, over the window's seconds."""
UNIT = "tokens/s"
BETTER = "higher"
SOURCE = "host_clock"


def read(run):
    return run.tokens_delivered() / run.window_s
