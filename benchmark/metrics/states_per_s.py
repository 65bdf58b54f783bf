"""Generated states of every check in the window over the window's seconds
(first check's start to last check's end): the upstream checker's own
throughput figure."""


def read(run):
    return sum(c.generated for c in run.checks) / run.window_s
